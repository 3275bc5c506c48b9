"""Attention in place (``docs/serving.md``, "Attend through the
table"): ``ops.paged_attention`` reads the pool through the block table
(the kernel in Pallas interpret mode on the CPU) and must agree with
the gathered form it replaces — ``gather_context`` into the jnp oracle
of ``ops.cached_attention`` (one query row) or into
``ops.chunk_cached_attention`` (several) — for every length a slot can
have, whatever its table looks like; and a server built on the table
path must serve the tokens the gathered path serves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.models import CacheRow
from apex_tpu.ops.decode_attention import (
    _reference,
    chunk_cached_attention,
    paged_attention,
    paged_attention_fits,
)
from apex_tpu.serving import InferenceServer, KVCacheConfig
from apex_tpu.serving.kv_cache import (
    BlockAllocator,
    CacheView,
    block_slots,
    context_bias,
    gather_context,
    init_kv_cache,
    read_slots,
    slot_index,
    write_prefill,
)

pytestmark = pytest.mark.serving

BS, NB, LAYERS = 16, 10, 2          # 160 positions: two 8-page windows
T = BS * NB


def _rand(rng, *shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _scene(start, rows, heads, d, dtype, seed=0):
    """A pool with four sequences in it and ``rows`` fresh rows each:

    0. ``start`` cached positions (the case under test), blocks in
       shuffled order, table entries beyond them 0 (the garbage block);
    1. an idle slot: position 0, an all-zero table;
    2. the full context less the fresh rows, blocks shuffled;
    3. shares sequence 2's first three physical blocks (a prefix-cache
       hit) and goes on in a block of its own."""
    rng = np.random.default_rng([seed, start, rows, heads])
    cfg = KVCacheConfig(LAYERS, heads, d, num_blocks=1 + 3 * NB,
                        block_size=BS, dtype=dtype)
    alloc = BlockAllocator(cfg)
    order = rng.permutation(alloc.alloc(3 * NB)).tolist()
    starts = np.array([start, 0, T - rows, 3 * BS + 5], np.int32)
    tables = np.zeros((4, NB), np.int32)
    for b in (0, 2):
        n = -(-(starts[b] + rows) // BS)
        tables[b, :n], order = order[:n], order[n:]
    tables[3, :3] = tables[2, :3]
    tables[3, 3] = order.pop()
    cache = init_kv_cache(cfg)
    for b in (0, 2, 3):             # 3 rewrites the shared blocks alike
        n = int(starts[b])
        if b == 3:                  # the shared prefix is 2's; own tail
            lo = 3 * BS
        else:
            lo = 0
        if n > lo:
            kv = tuple(_rand(rng, LAYERS, 1, n - lo, heads, d, dtype=dtype)
                       for _ in range(2))
            pos = jnp.arange(lo, n, dtype=jnp.int32)[None]
            cache = write_prefill(
                cache, kv, slot_index(jnp.asarray(tables[b:b + 1]), pos,
                                      BS))
    q = _rand(rng, 4, rows, heads, d, dtype=dtype)
    fresh = tuple(_rand(rng, 4, rows, heads, d, dtype=dtype)
                  for _ in range(2))
    pos = jnp.asarray(starts)[:, None] + jnp.arange(rows)[None]
    slots = slot_index(jnp.asarray(tables), pos, BS)
    return cfg, cache, jnp.asarray(tables), jnp.asarray(starts), slots, \
        q, fresh


def _oracle(cfg, cache, tables, starts, q, fresh, layer):
    """The gathered form: every sequence's whole context out of the
    pool, the fresh rows behind it, the ops as they always were."""
    k_ctx, v_ctx = gather_context(cache, tables, BS, cfg.num_heads)
    k = jnp.concatenate([k_ctx[layer], fresh[0]], axis=1)
    v = jnp.concatenate([v_ctx[layer], fresh[1]], axis=1)
    bias = context_bias(starts, T)
    if q.shape[1] == 1:
        bias = jnp.concatenate([bias, jnp.zeros((q.shape[0], 1))], axis=1)
        return _reference(q, k, v, bias, 1.0 / np.sqrt(cfg.head_dim))
    return chunk_cached_attention(q, k, v, bias)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("heads,dtype", [(25, jnp.bfloat16),
                                         (16, jnp.float32)])
@pytest.mark.parametrize("rows", [1, 5])
@pytest.mark.parametrize("start", [0, 1, BS - 1, BS, BS + 1, T - 5])
def test_table_path_matches_gathered_oracle(start, rows, heads, dtype):
    cfg, cache, tables, starts, slots, q, fresh = _scene(
        start, rows, heads, 64, dtype)
    view = CacheView(cache, tables, starts, slots, block_size=BS,
                     row=CacheRow.kv(heads, 64), table=True)
    layer = 1
    got, after = view.attend(layer, q, fresh)
    want = _oracle(cfg, cache, tables, starts, q, fresh, layer)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    # the fresh rows are where the table says, in the written layer only
    rows_at = read_slots(after.cache, slots.reshape(-1), heads)
    np.testing.assert_array_equal(
        np.asarray(rows_at["k"][layer], np.float32),
        np.asarray(fresh[0].reshape(-1, heads, 64), np.float32))
    np.testing.assert_array_equal(
        np.asarray(rows_at["v"][layer], np.float32),
        np.asarray(fresh[1].reshape(-1, heads, 64), np.float32))
    other = read_slots(cache, slots.reshape(-1), heads)["k"][0]
    np.testing.assert_array_equal(np.asarray(rows_at["k"][0], np.float32),
                                  np.asarray(other, np.float32))


@pytest.mark.parametrize("rows", [1, 5])
def test_gathered_view_is_the_oracle(rows):
    cfg, cache, tables, starts, slots, q, fresh = _scene(
        BS + 1, rows, 4, 64, jnp.float32)
    view = CacheView(cache, tables, starts, slots, block_size=BS,
                     row=CacheRow.kv(4, 64), table=False)
    got, after = view.attend(0, q, fresh)
    want = _oracle(cfg, cache, tables, starts, q, fresh, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
    table_view = CacheView(cache, tables, starts, slots, block_size=BS,
                           row=CacheRow.kv(4, 64), table=True)
    np.testing.assert_array_equal(
        np.asarray(after.cache["kv"]),
        np.asarray(table_view.attend(0, q, fresh)[1].cache["kv"]))


def test_kernel_refuses_a_pool_it_cannot_tile():
    assert paged_attention_fits(64, 16, jnp.bfloat16)
    assert paged_attention_fits(64, 8, jnp.float32)
    assert not paged_attention_fits(64, 8, jnp.bfloat16)   # half a tile
    assert not paged_attention_fits(32, 16, jnp.bfloat16)  # 64 lanes
    q = jnp.zeros((1, 1, 2, 32), jnp.float32)
    pool = jnp.zeros((1, 32, 2 * 2 * 32), jnp.float32)
    with pytest.raises(ValueError, match="cannot tile"):
        paged_attention(q, pool, 0, jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1,), jnp.int32), block_size=16)
    with pytest.raises(ValueError, match="pages must be"):
        paged_attention(q, pool[:, :, :64], 0,
                        jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1,), jnp.int32), block_size=16)


# -- a server on each path -------------------------------------------------

VOCAB = 89


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=160, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    params = models.GPTLMHeadModel(cfg).init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _serve(cfg, params):
    """Three waves through a server: chunked prefill of a prompt longer
    than two chunks, a periodic prompt whose n-gram drafts bring verify
    in, a block-aligned repeat (a copy-on-write), a two-token request
    (a plain decode launch), then a round trip of two cached blocks
    through export and import and one more request over them."""
    rng = np.random.default_rng(11)
    server = InferenceServer(cfg, params, max_batch_size=4,
                             max_context=160, block_size=16,
                             prefill_chunk=32, cache_dtype=jnp.float32)
    long = rng.integers(0, VOCAB, 80).tolist()
    periodic = rng.integers(0, VOCAB, 4).tolist() * 8
    wave1 = [long, periodic, rng.integers(0, VOCAB, 9).tolist()]
    out = server.generate(wave1, max_new_tokens=12)
    out += server.generate([list(long)], max_new_tokens=12)   # COW
    out += server.generate([wave1[2]], max_new_tokens=2)
    eng = server.engine
    held = eng.allocator.alloc(2)
    eng.import_blocks(held, eng.export_blocks([1, 2]))
    moved = read_slots(eng.cache, block_slots(held, 16), 2)
    kept = read_slots(eng.cache, block_slots([1, 2], 16), 2)
    for name in kept:
        np.testing.assert_array_equal(np.asarray(moved[name]),
                                      np.asarray(kept[name]))
    eng.allocator.free(held)
    out += server.generate([periodic + long[:20]], max_new_tokens=8)
    return server, out


def test_table_server_serves_what_the_gathered_server_serves(
        tiny, monkeypatch):
    cfg, params = tiny
    gathered, want = _serve(cfg, params)
    assert set(gathered.stats()["programs"]["attention"].values()) == \
        {"gathered"}
    import apex_tpu.serving.engine as engine_mod
    monkeypatch.setattr(engine_mod, "pallas_auto_gate", lambda: True)
    table, got = _serve(cfg, params)
    st = table.stats()
    assert set(st["programs"]["attention"].values()) == {"table"}
    assert got == want
    # every mechanism ran on the table path
    assert st["prefix_cow_blocks"] >= 1
    assert st["speculation"]["verify_steps"] >= 1
    assert st["speculation"]["decode_steps"] >= 1
    families = {k.split("[")[0] for k in st["programs"]["by_program"]}
    assert {"chunk_prefill_sampled", "decode_sampled", "verify_sampled",
            "copy_blocks", "import_blocks"} <= families
    assert table.engine.memory_info()["decode_temp_bytes"] is not None


def test_int8_pool_and_mesh_keep_the_gathered_path(tiny, monkeypatch):
    """What the engine can see decides: the gate says kernels, and an
    int8 pool, a mesh or a geometry the kernel cannot tile still gather."""
    from jax.sharding import Mesh
    cfg, params = tiny
    import apex_tpu.serving.engine as engine_mod
    monkeypatch.setattr(engine_mod, "pallas_auto_gate", lambda: True)
    kw = dict(max_batch_size=2, max_context=64)
    for extra in (dict(kv_quant="int8"),
                  dict(mesh=Mesh(np.asarray(jax.devices()[:1]),
                                 ("model",))),
                  dict(block_size=8)):       # half a bf16 sublane tile
        eng = engine_mod.DecodeEngine(cfg, params, **kw, **extra)
        assert set(eng.attention_paths.values()) == {"gathered"}, extra
    eng = engine_mod.DecodeEngine(cfg, params, **kw)
    assert eng.attention_paths["decode"] == "table"
