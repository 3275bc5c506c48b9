"""Hierarchical KV offload: host-RAM/disk tiers must accelerate, never
corrupt.

The load-bearing contract is BIT-EXACT parity: a server with the
offload tier enabled — demoting evicted prefix blocks to host RAM,
spilling to disk, promoting them back through the checksummed
``import_blocks`` path — must generate token-for-token what the same
params generate with the tier disabled, across session-resume traffic
that actually crosses every tier boundary (the counters prove it).
Every failure mode (torn spill, corrupt payload, promote-at-capacity,
transient import OOM) must degrade to cold prefill — slower, never
different — with the scheduler refcount invariant holding after every
step.

The store itself is pinned unit-style: LRU byte bound, spill-or-drop,
atomic write-tmp -> rename publishes, manifest verification deleting
torn entries whole, startup sweep/adoption.
"""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving import InferenceServer, SamplingParams
from apex_tpu.serving.kv_cache import BlockAllocator, KVCacheConfig
from apex_tpu.serving.offload import (
    KV_OFFLOAD_ENV,
    OffloadStore,
    merge_payloads,
    payload_nbytes,
    resolve_kv_offload,
    split_payload,
    verify_payload,
)
from apex_tpu.serving.prefix_cache import PrefixCache
from apex_tpu.utils.meters import CounterMeter

pytestmark = pytest.mark.serving

VOCAB = 61


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, params


# -- resolve / env twin ----------------------------------------------------

def test_resolve_kv_offload_values():
    assert resolve_kv_offload(None) is False
    assert resolve_kv_offload(True) is True
    assert resolve_kv_offload(False) is False
    for v in ("", "0", "off", "none", "false", "no"):
        assert resolve_kv_offload(v) is False, v
    for v in ("1", "on", "true", "yes", "ON", " Yes "):
        assert resolve_kv_offload(v) is True, v
    with pytest.raises(ValueError, match="KV offload"):
        resolve_kv_offload("sometimes")


def test_env_twin_fills_unset_kwarg_only(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setenv(KV_OFFLOAD_ENV, "1")
    on = InferenceServer(cfg, params, max_batch_size=2,
                         max_context=64, block_size=8,
                         cache_dtype=jnp.float32)
    assert on.kv_offload is True
    assert on.stats()["offload"]["enabled"] is True
    # a provided kwarg wins over the env
    off = InferenceServer(cfg, params, max_batch_size=2,
                          max_context=64, block_size=8,
                          cache_dtype=jnp.float32,
                          enable_kv_offload=False)
    assert off.kv_offload is False
    assert off.stats()["offload"]["enabled"] is False


# -- synthetic payloads (store unit tests need no model) -------------------

def _payload(seed, blocks=1, bs=4, rows=2):
    """A fake export_blocks payload: deterministic leaves + true crcs."""
    rng = np.random.RandomState(seed)
    leaves = {name: rng.rand(rows, blocks * bs).astype(np.float32)
              for name in ("k0", "v0")}
    return {
        "num_blocks": blocks,
        "block_size": bs,
        "leaves": leaves,
        "crc": {name: zlib.crc32(a.tobytes())
                for name, a in leaves.items()},
    }


def _key(i):
    return bytes([i]) * 16


def test_store_lru_byte_bound_drops_coldest_without_disk():
    one = payload_nbytes(_payload(0))
    store = OffloadStore(host_bytes=2 * one)
    for i in range(3):
        store.put(_key(i), _payload(i))
    # the coldest entry fell off; no disk tier -> counted as dropped
    assert store.host_entries == 2
    assert _key(0) not in store
    assert store.counters.count("host_dropped") == 1
    assert store.host_used_bytes <= store.host_bytes


def test_store_put_refreshes_recency_and_take_is_exclusive():
    one = payload_nbytes(_payload(0))
    store = OffloadStore(host_bytes=2 * one)
    store.put(_key(0), _payload(0))
    store.put(_key(1), _payload(1))
    store.put(_key(0), _payload(0))      # re-put: key 0 back to hot
    store.put(_key(2), _payload(2))      # key 1 is now the coldest
    assert _key(0) in store and _key(1) not in store
    payload, tier = store.take(_key(0))
    assert tier == "host"
    assert _key(0) not in store          # tiers exclusive: popped
    assert store.take(_key(0)) is None


def test_store_spills_coldest_to_disk_and_loads_back(tmp_path):
    one = payload_nbytes(_payload(0))
    store = OffloadStore(host_bytes=2 * one, spill_dir=str(tmp_path))
    for i in range(3):
        store.put(_key(i), _payload(i))
    assert store.counters.count("spills") == 1
    assert store.disk_entries == 1
    entry = tmp_path / _key(0).hex()
    assert (entry / "manifest.json").is_file()
    payload, tier = store.take(_key(0))
    assert tier == "disk"
    # verified load: bytes round-tripped exactly, entry consumed
    want = _payload(0)
    for name in want["leaves"]:
        np.testing.assert_array_equal(payload["leaves"][name],
                                      want["leaves"][name])
    verify_payload(payload)
    assert not entry.exists()
    assert store.disk_entries == 0


def test_store_torn_spill_reads_as_miss_and_is_deleted(tmp_path):
    one = payload_nbytes(_payload(0))
    store = OffloadStore(host_bytes=one, spill_dir=str(tmp_path))
    store.put(_key(0), _payload(0))
    store.put(_key(1), _payload(1))      # key 0 spills
    entry = tmp_path / _key(0).hex()
    leaf = entry / json.loads(
        (entry / "manifest.json").read_text())["leaves"]["k0"]["file"]
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0xFF                      # rot one payload byte
    leaf.write_bytes(bytes(raw))
    assert store.take(_key(0)) is None   # torn -> miss, never garbage
    assert store.counters.count("disk_torn") == 1
    assert not entry.exists()            # deleted whole


def test_store_sweeps_tmp_and_adopts_survivors(tmp_path):
    one = payload_nbytes(_payload(0))
    store = OffloadStore(host_bytes=one, spill_dir=str(tmp_path))
    store.put(_key(0), _payload(0))
    store.put(_key(1), _payload(1))      # key 0 published to disk
    # a crash mid-spill leaves a staged temp dir — never adopted
    stale = tmp_path / (".tmp-" + _key(9).hex())
    stale.mkdir()
    (stale / "leaf0.npy").write_bytes(b"half a write")
    reborn = OffloadStore(host_bytes=one, spill_dir=str(tmp_path))
    assert not stale.exists()
    assert reborn.disk_entries == 1      # restart keeps the cold tier
    payload, tier = reborn.take(_key(0))
    assert tier == "disk"
    verify_payload(payload)


def test_store_oversized_payload_never_wedges_the_lru(tmp_path):
    big = _payload(0, blocks=8)
    store = OffloadStore(host_bytes=payload_nbytes(big) // 2)
    store.put(_key(0), big)
    assert store.host_entries == 0
    assert store.counters.count("host_dropped") == 1
    spilling = OffloadStore(host_bytes=payload_nbytes(big) // 2,
                            spill_dir=str(tmp_path))
    spilling.put(_key(0), big)
    assert spilling.host_entries == 0 and spilling.disk_entries == 1


# -- payload helpers -------------------------------------------------------

def test_verify_payload_names_the_rotten_leaf():
    payload = _payload(3)
    payload["leaves"]["v0"].view(np.uint8).reshape(-1)[0] ^= 0xFF
    with pytest.raises(ValueError, match=r"leaf 'v0'.*rejected whole"):
        verify_payload(payload)
    verify_payload(_payload(3))          # pristine twin passes


def test_merge_then_split_round_trips_per_block():
    parts = [_payload(i) for i in range(3)]
    merged = merge_payloads(parts)
    assert merged["num_blocks"] == 3
    verify_payload(merged)
    back = split_payload(dict(merged, block_crc={
        name: [p["crc"][name] for p in parts]
        for name in merged["leaves"]}))
    for got, want in zip(back, parts):
        for name in want["leaves"]:
            np.testing.assert_array_equal(got["leaves"][name],
                                          want["leaves"][name])
        verify_payload(got)


def test_split_payload_carries_engine_recorded_crcs():
    """The integrity trap: split slices must carry the crcs RECORDED
    at export time, never recomputed from the slice bytes — a
    recompute would silently bless post-export rot."""
    parts = [_payload(i) for i in range(2)]
    merged = merge_payloads(parts)
    merged["block_crc"] = {name: [p["crc"][name] for p in parts]
                           for name in merged["leaves"]}
    # rot block 1's slice AFTER the per-block crcs were recorded
    # (byte column bs*4 is the first float32 byte of block 1's slots)
    bs = merged["block_size"]
    merged["leaves"]["k0"].view(np.uint8)[0, bs * 4] ^= 0xFF
    clean, torn = split_payload(merged)
    verify_payload(clean)                # block 0 untouched
    with pytest.raises(ValueError, match="rejected whole"):
        verify_payload(torn)             # block 1 convicted


# -- import_blocks checksum rejection (the shared integrity gate) ----------

def test_import_blocks_error_names_leaf_blocks_and_crcs(tiny):
    cfg, params = tiny
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=64, block_size=8,
                             cache_dtype=jnp.float32,
                             enable_kv_offload=False)
    server.generate([[1, 2, 3, 4, 5, 6, 7, 8, 9]], max_new_tokens=4)
    engine = server.engine
    payload = engine.export_blocks([1, 2])
    rotten = min(payload["leaves"])
    arr = payload["leaves"][rotten].copy()
    arr.view(np.uint8).reshape(-1)[0] ^= 0xFF
    payload["leaves"][rotten] = arr
    actual = zlib.crc32(np.ascontiguousarray(
        payload["leaves"][rotten]).tobytes())
    with pytest.raises(ValueError) as ei:
        engine.import_blocks([1, 2], payload)
    msg = str(ei.value)
    # the postmortem must carry WHICH leaf, WHICH blocks, BOTH crcs
    assert f"leaf {rotten!r}" in msg
    assert "[1, 2]" in msg
    assert f"{actual} (actual)" in msg
    assert f"{payload['crc'][rotten]} (expected)" in msg
    assert "rejected whole" in msg


# -- promote failure semantics (unit, fake engine) -------------------------

def _chain_fixture(importer=None, alloc_blocks=8):
    """A PrefixCache + real allocator + fake export/import closures:
    two registered chain blocks demoted into the store, ready to
    promote.  Returns (cache, allocator, store, counters, tokens)."""
    bs = 4
    alloc = BlockAllocator(KVCacheConfig(
        num_layers=1, num_heads=2, head_dim=4,
        num_blocks=alloc_blocks, block_size=bs, dtype=jnp.float32))
    cache = PrefixCache(alloc, bs)
    store = OffloadStore(host_bytes=1 << 20)
    off = CounterMeter()

    def exporter(ids):
        rng = np.random.RandomState(sum(ids))
        leaves = {"k0": rng.rand(2, len(ids) * bs).astype(np.float32)}
        return {
            "num_blocks": len(ids), "block_size": bs, "leaves": leaves,
            "crc": {"k0": zlib.crc32(leaves["k0"].tobytes())},
            "block_crc": {"k0": [
                zlib.crc32(np.ascontiguousarray(
                    leaves["k0"][:, i * bs:(i + 1) * bs]).tobytes())
                for i in range(len(ids))]},
        }

    imports = []
    cache.attach_offload(
        store, exporter,
        importer or (lambda ids, p: imports.append((list(ids), p))),
        counters=off)
    tokens = list(range(2 * bs))
    blocks = alloc.alloc(2)
    from apex_tpu.serving.prefix_cache import ROOT
    assert cache.register(ROOT, tuple(tokens[:bs]), blocks[0])
    assert cache.register(blocks[0], tuple(tokens[bs:]), blocks[1])
    alloc.free(blocks)                   # -> evictable LRU holds
    assert cache.evict(2) == 2           # -> demoted into the store
    assert off.count("demotes") == 2
    assert len(store) == 2
    cache.audit()
    return cache, alloc, store, off, tokens


def test_promote_at_capacity_puts_every_payload_back():
    cache, alloc, store, off, tokens = _chain_fixture()
    matched = []
    assert cache.promote(tokens, matched, lambda n: None) == 0
    assert matched == []
    assert off.count("capacity_skips") == 1
    assert len(store) == 2               # payloads kept warm
    cache.audit()


def test_promote_import_oom_puts_back_and_frees_fresh_blocks():
    def oom_importer(ids, payload):
        raise MemoryError("transient scatter OOM")
    cache, alloc, store, off, tokens = _chain_fixture(oom_importer)
    free_before = alloc.num_free
    matched = []
    assert cache.promote(tokens, matched, alloc.alloc) == 0
    assert matched == []
    assert off.count("capacity_skips") == 1
    assert len(store) == 2               # payloads kept warm
    assert alloc.num_free == free_before  # fresh blocks not leaked
    cache.audit()


def test_promote_happy_path_registers_the_whole_run():
    cache, alloc, store, off, tokens = _chain_fixture()
    matched = []
    assert cache.promote(tokens, matched, alloc.alloc) == 2
    assert len(matched) == 2
    assert off.count("promotes_host") == 2
    assert len(store) == 0               # tiers exclusive
    # the promoted run carries match()'s one-ref-per-block contract
    assert all(alloc.refs(b) == 1 for b in matched)
    cache.audit()


def test_promote_rejects_corrupt_payload_whole_and_cold_prefills():
    cache, alloc, store, off, tokens = _chain_fixture()
    for key in list(store._host):
        store._host[key]["leaves"]["k0"].view(
            np.uint8).reshape(-1)[0] ^= 0xFF
    matched = []
    assert cache.promote(tokens, matched, alloc.alloc) == 0
    assert matched == []
    assert off.count("crc_rejects") == 1  # first chunk convicted
    assert len(store) == 1                # corrupt entry discarded
    cache.audit()


# -- server-level parity across tier crossings -----------------------------

def _server(cfg, params, offload, num_blocks, **kw):
    kw.setdefault("kv_offload_host_bytes", 8 << 20)
    return InferenceServer(
        cfg, params, max_batch_size=2, max_context=128, block_size=8,
        cache_dtype=jnp.float32, enable_prefix_cache=True,
        enable_kv_offload=offload,
        num_blocks=num_blocks, **kw)


def _sessions(n, rng):
    """n distinct session prompts: 40-token prefix + 3-token tail
    (5 full blocks each at block_size 8)."""
    return [list(rng.randint(0, VOCAB, size=43)) for _ in range(n)]


def _session_traffic(server, prompts, sampling=None):
    """Two passes, one request at a time (so each session's blocks
    release — and with offload, demote — before the next session needs
    the pool), scheduler invariant audited every step.  Pass 2 resumes
    every session with its own pass-1 prompt."""
    outs = []
    for _pass in range(2):
        for i, p in enumerate(prompts):
            sp = None if sampling is None else sampling(i)
            req = server.submit(p, 6, sampling=sp)
            while server.has_work:
                server.step()
                server.scheduler.audit()
                if server.prefill_scheduler is not None:
                    server.prefill_scheduler.audit()
            outs.append(list(req.generated))
    return outs


def _assert_parity(got, want, tag):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        for t, (x, y) in enumerate(zip(a, b)):
            assert x == y, (f"{tag}: request {i} diverged at token "
                            f"{t}: offload={x} baseline={y}")
        assert len(a) == len(b), (tag, i)


def test_server_parity_greedy_across_demote_promote(tiny):
    cfg, params = tiny
    rng = np.random.RandomState(7)
    prompts = _sessions(4, rng)
    # pool of 13 blocks vs 4 sessions x 6 blocks: pass 1 evicts —
    # offload-on demotes — every finished session; pass 2 promotes
    on = _server(cfg, params, True, 13)
    got = _session_traffic(on, prompts)
    st = on.stats()["offload"]
    assert st["demotes"] > 0, "workload never crossed device -> host"
    assert st["promotes_host"] > 0, "workload never promoted back"
    assert st["crc_rejects"] == 0
    off = _server(cfg, params, False, 13)
    want = _session_traffic(off, prompts)
    _assert_parity(got, want, "greedy")


def test_server_parity_stochastic_sampling(tiny):
    """Counter-keyed sampling: seeded stochastic output must be as
    oblivious to tier crossings as greedy is."""
    cfg, params = tiny

    def sampling(i):
        return SamplingParams(temperature=0.8, top_k=13, top_p=0.9,
                              seed=1000 + i)

    rng = np.random.RandomState(11)
    prompts = _sessions(4, rng)
    on = _server(cfg, params, True, 13)
    got = _session_traffic(on, prompts, sampling)
    assert on.stats()["offload"]["promotes_host"] > 0
    off = _server(cfg, params, False, 13)
    want = _session_traffic(off, prompts, sampling)
    _assert_parity(got, want, "stochastic")


def test_server_parity_through_disk_tier(tiny, tmp_path):
    """A host tier too small to hold one session forces every demote
    through the spill path; promotes come back from DISK, parity
    still bit-exact."""
    cfg, params = tiny
    rng = np.random.RandomState(13)
    prompts = _sessions(4, rng)
    on = _server(cfg, params, True, 13,
                 kv_offload_host_bytes=8 << 10,
                 kv_offload_dir=str(tmp_path))
    got = _session_traffic(on, prompts)
    st = on.stats()["offload"]
    assert st["spills"] > 0, "host tier never spilled"
    assert st["promotes_disk"] > 0, "no promote came back from disk"
    off = _server(cfg, params, False, 13)
    want = _session_traffic(off, prompts)
    _assert_parity(got, want, "disk-tier")


def test_server_corrupt_spill_cold_prefills_bit_identically(tiny,
                                                            tmp_path):
    """Rot every on-disk spill between the passes: promotes must turn
    into verified misses (``disk_torn``) and pass 2 must cold-prefill
    to the exact offload-off tokens."""
    cfg, params = tiny
    rng = np.random.RandomState(17)
    prompts = _sessions(3, rng)
    # host_bytes=0: every demote publishes straight to disk, so the
    # rot below covers the WHOLE store (a bounded host tier would
    # launder still-hot entries to disk clean, after the rot)
    on = _server(cfg, params, True, 13,
                 kv_offload_host_bytes=0,
                 kv_offload_dir=str(tmp_path))
    got = []
    for p in prompts:                    # pass 1: populate the tiers
        req = on.submit(p, 6)
        while on.scheduler.has_work:
            on.step()
            on.scheduler.audit()
        got.append(list(req.generated))
    # demote EVERY still-evictable chain to disk first, so the rot
    # below covers all three sessions (traffic alone only evicts —
    # and therefore spills — the coldest one)
    on.prefix_cache.evict(1000)
    assert on.stats()["offload"]["spills"] >= 3 * 5
    for entry in tmp_path.iterdir():     # rot every spilled leaf
        for f in entry.glob("*.npy"):
            raw = bytearray(f.read_bytes())
            raw[-1] ^= 0xFF
            f.write_bytes(bytes(raw))
    for p in prompts:                    # pass 2: resumed sessions
        req = on.submit(p, 6)
        while on.scheduler.has_work:
            on.step()
            on.scheduler.audit()
        got.append(list(req.generated))
    st = on.stats()["offload"]
    assert st["disk_torn"] > 0, "no spill was convicted"
    assert st["promotes_disk"] == 0, "a torn spill promoted"
    off = _server(cfg, params, False, 13)
    want = _session_traffic(off, prompts)
    _assert_parity(got, want, "corrupt-spill")


def test_server_offload_requires_prefix_cache(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="prefix cache"):
        InferenceServer(cfg, params, max_batch_size=2,
                        max_context=64, block_size=8,
                        cache_dtype=jnp.float32,
                        enable_prefix_cache=False,
                        enable_kv_offload=True)


def test_server_parity_disagg_prefill_pool_is_cache_home(tiny):
    """Disaggregated mode: demotes export from and promotes import
    into the PREFILL pool (the cache home), parity vs a monolithic
    offload-off server."""
    cfg, params = tiny
    rng = np.random.RandomState(19)
    prompts = _sessions(4, rng)
    on = InferenceServer(
        cfg, params, max_batch_size=2, max_context=128, block_size=8,
        cache_dtype=jnp.float32, enable_prefix_cache=True,
        enable_disagg=True,
        disagg_prefill_blocks=17, enable_kv_offload=True)
    got = _session_traffic(on, prompts)
    st = on.stats()["offload"]
    assert st["demotes"] > 0 and st["promotes_host"] > 0
    off = _server(cfg, params, False, 13)
    want = _session_traffic(off, prompts)
    _assert_parity(got, want, "disagg")


def test_promote_with_nothing_to_promote_is_clean():
    """A promote walk that finds nothing — store miss on the first
    missing chunk, or a run the device tier already fully matched —
    returns 0 WITHOUT allocating, importing, or a spurious
    ``capacity_skips`` (an empty block list is a no-op, not a
    failure)."""
    cache, alloc, store, off, tokens = _chain_fixture()
    free_before = alloc.num_free
    # chunks that were never demoted: the store probe misses at once
    cold = [100 + t for t in range(len(tokens))]
    matched = []
    assert cache.promote(cold, matched, alloc.alloc) == 0
    assert matched == []
    assert off.count("capacity_skips") == 0, \
        "an empty walk is not an at-capacity skip"
    assert off.count("crc_rejects") == 0
    assert alloc.num_free == free_before, \
        "no device blocks may be reserved for an empty run"
    # a run the device tier already covers short-circuits the same way
    full = list(range(len(tokens) // 4))
    assert cache.promote(tokens, full, alloc.alloc) == 0
    assert off.count("capacity_skips") == 0
    assert len(store) == 2               # payloads untouched
    cache.audit()
