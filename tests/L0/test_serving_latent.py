"""The latent pool under ``DecodeEngine`` and ``InferenceServer``: the
same step loop, scheduler, allocator, prefix cache, chunked prefill and
speculation as a ``GPTConfig`` gets, told apart by the configuration
object alone.  Tiny sizes, float32 weights and pool, on the CPU; the
table path in Pallas interpret mode is in ``test_deepseek_model.py``
(absorbed against expanded) and, against the gathered path, here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.models import CacheRow
from apex_tpu.serving import InferenceServer, KVCacheConfig
from apex_tpu.serving.engine import DecodeEngine
from apex_tpu.serving.kv_cache import (
    BlockAllocator,
    CacheView,
    copy_blocks,
    init_kv_cache,
    read_blocks,
    slot_index,
    write_blocks,
    write_layer,
)
from benchmarks.harness import weights
from benchmarks.reference import deepseek_v3 as ref

pytestmark = pytest.mark.serving

SIZES = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, max_position_embeddings=256,
    rope_theta=1e6, rope_interleave=True, rms_norm_eps=1e-6,
    routed_scaling_factor=2.448, norm_topk_prob=True)
REF_SIZES = dict(SIZES, assumed={"initializer_range": 0.02},
                 reference_longest_row=64)
CFG = models.DeepseekV3Config(**SIZES)
BS = 8


@pytest.fixture(scope="module")
def params():
    p = weights.make_params(ref.param_table(REF_SIZES), 21, jnp.float32, 0.2)
    p["block_2"]["moe"]["e_score_correction_bias"] = jnp.asarray(
        np.random.default_rng(21).normal(size=8) * 0.3, jnp.float32)
    return p


@pytest.fixture(scope="module")
def reference_logits(params):
    fn = jax.jit(lambda ids: ref.logits(params, ids, REF_SIZES))
    return lambda ids: np.asarray(fn(jnp.asarray([ids], jnp.int32)))[0]


def _engine(params, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", BS)
    kw.setdefault("cache_dtype", jnp.float32)
    return DecodeEngine(CFG, params, **kw)


def _server(params, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", BS)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("prefill_chunk", 16)
    return InferenceServer(CFG, params, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


# -- prefill and decode through the cache against the full forward pass --------

@pytest.mark.parametrize("n", [7, 16, 17, 33])
def test_chunked_prefill_then_decode_match_the_references_full_pass(
        params, reference_logits, n):
    """Chunks of 16 over blocks of 8: a prompt inside one chunk, one
    that ends on a chunk's and a block's edge, one a token past it, one
    over three chunks.  Then four decode steps fed the reference's own
    tokens.  Logits at every step against ONE full pass of the plain
    reference over the whole row."""
    with jax.default_matmul_precision("highest"):
        e = _engine(params)
        prompt = _prompt(n, n)
        blocks = e.allocator.alloc(e.blocks_per_seq)
        last = None
        for start in range(0, n, 16):
            last = e.chunk_prefill(prompt[start:start + 16], start, blocks,
                                   pad_to=16)
        row, got = list(prompt), [np.asarray(last)]
        tables = np.zeros((2, e.blocks_per_seq), np.int32)
        tables[0] = blocks
        for _ in range(4):
            row.append(int(np.argmax(got[-1])))
            logits = e.decode([row[-1], 0], [len(row) - 1, 0], tables)
            got.append(np.asarray(logits)[0])
    want = reference_logits(row)
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[n - 1 + i], atol=3e-4, rtol=3e-4)
    assert e.attention_paths == dict.fromkeys(
        ("decode", "verify", "chunk_prefill"), "gathered")


def test_verify_rows_match_the_references_full_pass(params,
                                                    reference_logits):
    """Two slots at different depths, five fed rows each (one slot only
    three valid): every row's logits."""
    with jax.default_matmul_precision("highest"):
        e = _engine(params)
        rows = [_prompt(31, 20), _prompt(32, 11)]
        tables = np.zeros((2, e.blocks_per_seq), np.int32)
        fed = [15, 8]                       # cached before the verify
        for i, r in enumerate(rows):
            blocks = e.allocator.alloc(e.blocks_per_seq)
            tables[i] = blocks
            e.chunk_prefill(r[:fed[i]], 0, blocks, pad_to=16)
        tokens = np.zeros((2, 5), np.int32)
        tokens[0] = rows[0][15:20]
        tokens[1, :3] = rows[1][8:11]
        logits = np.asarray(e.verify(tokens, [5, 3], fed, tables))
    for i, (r, k) in enumerate(zip(rows, (5, 3))):
        want = reference_logits(r)
        np.testing.assert_allclose(logits[i, :k],
                                   want[fed[i]:fed[i] + k], atol=3e-4,
                                   rtol=3e-4)


def test_the_chunk_width_does_not_move_the_rows(params):
    """A prompt fed in chunks of 16 and fed whole in one chunk of 32
    leaves the same rows in the pool, and both end on the logits of the
    model's plain forward pass (the expanded form) over the prompt."""
    prompt = _prompt(41, 21)
    pools, lasts = [], []
    with jax.default_matmul_precision("highest"):
        for width in (16, 32):
            e = _engine(params)
            blocks = e.allocator.alloc(e.blocks_per_seq)
            for start in range(0, 21, width):
                last = e.chunk_prefill(prompt[start:start + width], start,
                                       blocks, pad_to=width)
            slots = np.asarray(slot_index(
                jnp.asarray([blocks], jnp.int32),
                jnp.arange(21, dtype=jnp.int32)[None], BS))[0]
            pools.append(np.asarray(e.cache["kv"])[:, slots])
            lasts.append(np.asarray(last))
        plain = np.asarray(jax.jit(CFG.build_model().apply)(
            {"params": params}, jnp.asarray([prompt], jnp.int32)))[0, -1]
    np.testing.assert_allclose(pools[0], pools[1], atol=2e-5)
    assert pools[0].any()
    for last in lasts:
        np.testing.assert_allclose(last, plain, atol=3e-4, rtol=3e-4)


# -- through InferenceServer -----------------------------------------------------

def _greedy_by_reference(reference_logits, prompt, new):
    row = list(prompt)
    for _ in range(new):
        row.append(int(np.argmax(reference_logits(row)[-1])))
    return row[len(prompt):]


def test_the_server_serves_the_family_with_its_defaults(
        params, reference_logits):
    """Chunked prefill, the pipelined loop, speculation and the prefix
    cache as they come; the tokens are the reference's greedy ones."""
    with jax.default_matmul_precision("highest"):
        srv = _server(params)
        prompts = [_prompt(51, 37), _prompt(52, 9)]
        outs = srv.generate(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        assert o == _greedy_by_reference(reference_logits, p, 6)
    st = srv.stats()
    assert st["memory"]["cache_kind"] == "latent"
    # 128 stored values of float32 a token and layer: 48 kept, padded
    assert st["memory"]["row_bytes_per_token_layer"] == 128 * 4
    assert st["memory"]["pool_bytes"] == srv.engine.cache_cfg.num_blocks \
        * BS * 3 * 128 * 4
    assert set(k.split("[")[0] for k in st["programs"]["by_program"]) \
        >= {"chunk_prefill_sampled", "decode_sampled"}


def test_a_second_request_on_a_shared_prefix_hits_and_copies_on_write(
        params):
    """A block-aligned repeat: the whole prompt is found in the prefix
    cache, and the block the first new token is written into is cloned
    first.  The latent pool's rows move as any leaf's do."""
    with jax.default_matmul_precision("highest"):
        srv = _server(params)
        prompt = _prompt(61, 32)                    # four whole blocks
        first = srv.generate([prompt], max_new_tokens=5)[0]
        again = srv.generate([prompt], max_new_tokens=5)[0]
        longer = srv.generate([prompt + _prompt(62, 5)],
                              max_new_tokens=3)[0]
        fresh = _server(params, enable_prefix_cache=False).generate(
            [prompt + _prompt(62, 5)], max_new_tokens=3)[0]
    assert again == first and longer == fresh
    st = srv.stats()
    assert st["prefix_hit_tokens"] >= 24 + 32
    assert st["prefix_cow_blocks"] >= 1
    assert st["prefix_hit_requests"] == 2


def test_preemption_and_re_prefill_leave_the_tokens_as_they_were(params):
    """A pool too small for both requests' whole rows: one is preempted
    and prefilled again; the tokens are a roomy server's."""
    prompts = [_prompt(71, 30), _prompt(72, 28)]
    with jax.default_matmul_precision("highest"):
        tight = _server(params, num_blocks=9, enable_prefix_cache=False)
        got = tight.generate(prompts, max_new_tokens=14)
        roomy = _server(params, enable_prefix_cache=False)
        want = roomy.generate(prompts, max_new_tokens=14)
    assert got == want
    assert tight.stats()["preemptions"] >= 1
    assert roomy.stats()["preemptions"] == 0


def test_stats_count_the_rows_each_expert_was_given(params):
    with jax.default_matmul_precision("highest"):
        srv = _server(params, enable_speculation=False,
                      enable_prefix_cache=False)
        prompts = [_prompt(81, 19), _prompt(82, 5)]
        srv.generate(prompts, max_new_tokens=4)
    ex = srv.stats()["experts"]
    # every prompt token and every generated token but the last went
    # through both expert layers, two experts each; idle slots' and
    # padding rows' routes are not counted
    tokens = 19 + 5 + 2 * 3
    assert ex["enabled"] and ex["layers"] == 2 and ex["experts_held"] == 8
    assert ex["rows_routed"] == tokens * 2 * 2
    assert [sum(layer) for layer in ex["routed"]] == [tokens * 2] * 2
    for layer, ratio in zip(ex["routed"], ex["max_over_mean"]):
        assert ratio == pytest.approx(max(layer) / (sum(layer) / 8),
                                      abs=1e-3)
    srv.engine.reset_cache()
    assert srv.stats()["experts"]["rows_routed"] == 0
    # a family without expert layers says so
    gpt = models.GPTConfig(vocab_size=64, hidden_size=32,
                           num_hidden_layers=1, num_attention_heads=2,
                           intermediate_size=64,
                           max_position_embeddings=32)
    gp = gpt.build_model().init(jax.random.key(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
    st = InferenceServer(gpt, gp, max_batch_size=1).stats()
    assert st["experts"] == {"enabled": False}
    assert st["memory"]["cache_kind"] == "kv"
    assert st["memory"]["row_bytes_per_token_layer"] == 2 * 32 * 2


# -- the seam's refusals, and what is left alone -----------------------------------

def test_what_the_latent_pool_does_not_do_yet_refuses_with_its_reason(
        params):
    with pytest.raises(NotImplementedError,
                       match="kv_quant='int8' stores one scale a head"):
        _engine(params, kv_quant="int8")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("model",))
    with pytest.raises(NotImplementedError,
                       match="shards the pool's row by whole heads"):
        _engine(params, mesh=mesh)
    with pytest.raises(NotImplementedError, match="ROADMAP.md Reach"):
        _server(params, kv_quant="int8")


def test_offload_and_disaggregation_take_the_latent_pool_as_it_is(params):
    """They move whole blocks of a leaf and depend on no head: the
    hand-off payload carries the rows and not the counters."""
    prompts = [_prompt(91, 26)]
    with jax.default_matmul_precision("highest"):
        want = _server(params).generate(prompts, max_new_tokens=5)
        srv = _server(params, enable_disagg=True)
        assert srv.generate(prompts, max_new_tokens=5) == want
        assert srv.stats()["experts"]["rows_routed"] == (26 + 4) * 2 * 2
        e = srv.engine
        payload = e.export_blocks([1, 2])
    assert set(payload["leaves"]) == {"kv"}
    assert payload["leaves"]["kv"].shape == (3, 2 * BS, 128)
    e.import_blocks([3, 4], payload)
    np.testing.assert_array_equal(
        np.asarray(e.cache["kv"])[:, 3 * BS:5 * BS],
        np.asarray(e.cache["kv"])[:, BS:3 * BS])


@pytest.mark.parametrize("width", [128, 640])
def test_the_block_functions_work_on_a_leaf_of_any_width(width):
    """``write_layer``, ``copy_blocks`` (copy-on-write),
    ``read_blocks`` / ``write_blocks`` and the byte accounting, on a row
    of one group; a counter beside the pool is carried through."""
    cfg = KVCacheConfig(num_layers=2, num_heads=1, head_dim=width // 2,
                        num_blocks=6, block_size=BS, dtype=jnp.float32)
    assert cfg.row_width == width
    assert cfg.bytes_per_block == 2 * BS * width * 4
    assert cfg.row_bytes == width * 4
    assert cfg.bytes() == 6 * cfg.bytes_per_block
    cache = dict(init_kv_cache(cfg), routed=jnp.ones((1, 4), jnp.int32))
    assert cache["kv"].shape == (2, 6 * BS, width)
    rows = jnp.asarray(np.random.default_rng(width).normal(
        size=(1, BS, width - 20)), jnp.float32)       # 20 lanes of padding
    slots = (BS + jnp.arange(BS, dtype=jnp.int32))[None]
    cache = write_layer(cache, 1, rows, slots)
    got = np.asarray(cache["kv"])
    np.testing.assert_array_equal(got[1, BS:2 * BS, :width - 20],
                                  np.asarray(rows)[0])
    assert not got[1, BS:2 * BS, width - 20:].any() and not got[0].any()
    cache = copy_blocks(cache, jnp.asarray([1, 0], jnp.int32),
                        jnp.asarray([4, 0], jnp.int32), BS)
    np.testing.assert_array_equal(np.asarray(cache["kv"])[:, 4 * BS:5 * BS],
                                  got[:, BS:2 * BS])
    assert np.asarray(cache["routed"]).tolist() == [[1, 1, 1, 1]]
    leaves = read_blocks(cache, jnp.asarray([4], jnp.int32), BS)
    assert set(leaves) == {"kv"}
    cache = write_blocks(cache, jnp.asarray([5], jnp.int32), leaves, BS)
    np.testing.assert_array_equal(np.asarray(cache["kv"])[:, 5 * BS:],
                                  got[:, BS:2 * BS])
    assert BlockAllocator(cfg).num_free == 5


@pytest.mark.parametrize("rows,start", [(1, 0), (1, 21), (5, 7), (5, 16),
                                        (24, 3)])
def test_the_table_path_interpreted_matches_the_gathered_path(rows, start):
    """``CacheView.attend`` on a latent row of two lane tiles, four
    heads: Pallas in interpret mode through the block table against the
    gathered jnp form, decode, verify and chunk alike; both write the
    same rows."""
    rng = np.random.default_rng(rows * 100 + start)
    row = CacheRow.latent(128, 16, 4)
    cfg = KVCacheConfig(num_layers=2, num_heads=1, head_dim=128,
                        num_blocks=9, block_size=BS, dtype=jnp.float32)
    cache = init_kv_cache(cfg)
    cache["kv"] = jnp.asarray(rng.normal(size=cache["kv"].shape),
                              jnp.float32).at[..., 144:].set(0.0)
    tables = jnp.asarray([[3, 1, 4, 2, 7, 5, 0, 0]], jnp.int32)
    starts = jnp.asarray([start], jnp.int32)
    pos = start + jnp.arange(rows, dtype=jnp.int32)[None]
    slots = slot_index(tables, pos, BS)
    q = jnp.asarray(rng.normal(size=(1, rows, 4, 144)), jnp.float32)
    fresh = jnp.asarray(rng.normal(size=(1, rows, 144)), jnp.float32)
    outs = []
    for table in (False, True):
        view = CacheView(cache, tables, starts, slots, block_size=BS,
                         row=row, table=table)
        ctx, after = view.attend(1, q, fresh, scale=0.17)
        assert ctx.shape == (1, rows, 4, 128)
        outs.append((np.asarray(ctx), np.asarray(after.cache["kv"])))
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_a_prefix_chain_deeper_than_the_interpreters_stack_is_evicted():
    """A prompt of 16k tokens is a chain of 1,040 blocks in the prefix
    cache's tree; evicting its head frees them all (the recursion this
    replaced stopped the first run of the long-document cell on the
    chip)."""
    import sys

    from apex_tpu.serving.prefix_cache import ROOT, PrefixCache
    deep = sys.getrecursionlimit() + 100
    cfg = KVCacheConfig(num_layers=1, num_heads=1, head_dim=64,
                        num_blocks=deep + 2, block_size=2)
    alloc = BlockAllocator(cfg)
    cache = PrefixCache(alloc, 2)
    blocks = alloc.alloc(deep)
    parent = ROOT
    for i, blk in enumerate(blocks):
        assert cache.register(parent, (i, i + 1), blk)
        parent = blk
    alloc.free(blocks)                       # held by the cache now
    assert cache.num_evictable == deep and alloc.num_free == 1
    assert cache.evict(1) == deep            # the head takes its subtree
    assert cache.num_cached_blocks == 0 and alloc.num_free == deep + 1
    cache.audit()


def test_on_an_accelerator_verify_is_compiled_beside_decode(params,
                                                            monkeypatch):
    """The first decode launch of a kind is followed by one idle launch
    of the verify program of that kind (skipped on the CPU backend,
    which this test overrides): the program is there before the first
    draft, the idle rows write to the garbage block, count no routed row
    and change no token."""
    from apex_tpu.serving.scheduler import SamplingParams
    prompts = [_prompt(95, 20), _prompt(96, 9)]
    sampling = [None, SamplingParams(temperature=0.8, top_p=0.95, seed=3)]

    def serve():
        srv = _server(params)
        reqs = [srv.submit(p, 5, sampling=s)
                for p, s in zip(prompts, sampling)]
        while srv.has_work:
            srv.step()
        return srv, [list(r.generated) for r in reqs]

    with jax.default_matmul_precision("highest"):
        plain, want = serve()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ahead, got = serve()
    assert got == want

    def families(srv):
        return {k.split("[")[0]: v["calls"] for k, v in
                srv.stats()["programs"]["by_program"].items()}

    assert "verify_stoch" not in families(plain)
    assert families(ahead)["verify_stoch"] == 1
    assert ahead.stats()["speculation"]["verify_steps"] \
        == plain.stats()["speculation"]["verify_steps"]
    assert ahead.stats()["experts"]["routed"] \
        == plain.stats()["experts"]["routed"]
