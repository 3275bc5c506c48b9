"""Quantized int8 KV cache (``docs/serving.md``, "Quantized KV cache").

Two gates, mirroring the repo's oracle style:

1. a decode-parity TOLERANCE oracle — quantization is lossy by
   design, so quant-on generation is held to a pinned token-agreement
   budget against the full-width pool, never bit-equality;
2. exact BIT-STABILITY of quant-on runs against themselves — the same
   quant-on computation must produce identical tokens under forced
   preemption, prefix-cache eviction, COW hits, chunked prefill,
   speculation rollback, the pipelined loop, and tensor parallelism,
   because every K/V value quantizes at projection (elementwise,
   batch-shape independent) and every read dequantizes the same
   bytes.

Plus the unit tier for the primitives themselves: absmax round-trip
error bound, the all-zero scale=0 guard, bf16-vs-fp32 dequant
consistency, and Pallas-kernel-vs-jnp-oracle agreement on int8 inputs
(the in-kernel dequant must equal dequantize-then-attend bit-for-bit
on both paths).

Runs on the emulated 8-device CPU mesh (``tests/conftest.py``) so the
tp axes exercise the head-sharded scale sidecar.  The heavier
non-acceptance stability oracles are ``slow``-marked to respect the
saturated tier-1 wall budget (the ``test_router.py`` precedent); the
build-matrix ``kv_quant`` axis runs this file in FULL, slow tier
included.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from apex_tpu import models
from apex_tpu.ops.decode_attention import cached_attention, \
    chunk_cached_attention
from apex_tpu.ops.kv_quant import INT8_QMAX, dequantize_kv, quantize_kv
from apex_tpu.serving import InferenceServer, KVCacheConfig
from apex_tpu.serving.kv_cache import resolve_cache_dtype, \
    resolve_kv_quant, scale_sidecar

pytestmark = pytest.mark.serving

VOCAB = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("kv_quant", "int8")
    return InferenceServer(cfg, params, **kw)


def _audited_generate(server, prompts, n, **kw):
    reqs = [server.submit(p, n, **kw) for p in prompts]
    while server.scheduler.has_work:
        server.step()
        server.scheduler.audit()
    return [list(r.generated) for r in reqs]


def _lcp(a, b):
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# -- unit tier: the quantize/dequantize primitives --------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_roundtrip_error_bound(dtype):
    """Absmax symmetric int8: per-vector round-trip error is bounded
    by half a quantization step (scale/2 = absmax/254) plus the input
    dtype's own representation error."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 7, 3, 16) * 3.0, dtype)
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    back = dequantize_kv(q, scale, jnp.float32)
    err = np.abs(np.asarray(back)
                 - np.asarray(x.astype(jnp.float32)))
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert np.all(err <= bound), \
        f"round-trip error {err.max()} exceeds half-step bound"
    # the grid is symmetric: quantizing -x is exactly -q, same scale
    qn, sn = quantize_kv(-x)
    assert np.array_equal(np.asarray(qn), -np.asarray(q))
    assert np.array_equal(np.asarray(sn), np.asarray(scale))


def test_quantize_all_zero_vector_scale_zero_no_nan():
    """An all-zero K/V vector (an unwritten slot, a zeroed pool) must
    quantize to (0, scale=0) through the gated inverse — no division,
    no NaN — and dequantize to exact zeros."""
    x = jnp.zeros((2, 3, 4, 8), jnp.float32)
    q, scale = quantize_kv(x)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(scale) == 0.0)
    back = dequantize_kv(q, scale, jnp.bfloat16)
    assert np.all(np.isfinite(np.asarray(back, np.float32)))
    assert np.all(np.asarray(back, np.float32) == 0.0)
    # a mixed batch: one zero row among live rows stays exact
    y = x.at[0, 0, 0].set(jnp.arange(8, dtype=jnp.float32))
    q2, s2 = quantize_kv(y)
    assert float(s2[0, 0, 0]) > 0 and float(s2[1, 0, 0]) == 0.0
    assert np.all(np.isfinite(
        np.asarray(dequantize_kv(q2, s2, jnp.float32))))


def test_dequant_bf16_vs_fp32_compute_dtype_parity():
    """The dequant path multiplies in fp32 and casts ONCE: the bf16
    compute dtype sees exactly the fp32 product rounded to bf16 —
    never a bf16 multiply of a bf16 cast."""
    rng = np.random.RandomState(1)
    q, scale = quantize_kv(jnp.asarray(rng.randn(5, 6, 2, 32),
                                       jnp.float32))
    f32 = dequantize_kv(q, scale, jnp.float32)
    bf16 = dequantize_kv(q, scale, jnp.bfloat16)
    assert bf16.dtype == jnp.bfloat16
    assert np.array_equal(
        np.asarray(f32.astype(jnp.bfloat16), np.float32),
        np.asarray(bf16, np.float32))


def test_quantize_deterministic_across_batching():
    """The same vector quantizes to the same bytes however the write
    was batched — the property chunked prefill, decode singles, and
    verify columns all lean on for bit-stability."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 24, 2, 16), jnp.float32)
    q_all, s_all = quantize_kv(x)
    for lo, hi in ((0, 7), (7, 16), (16, 24)):
        q_c, s_c = quantize_kv(x[:, lo:hi])
        assert np.array_equal(np.asarray(q_c),
                              np.asarray(q_all[:, lo:hi]))
        assert np.array_equal(np.asarray(s_c),
                              np.asarray(s_all[:, lo:hi]))


def test_pallas_kernel_matches_jnp_oracle_on_quantized_inputs():
    """In-kernel dequant is EXACTLY dequantize-then-attend on both
    paths (bit-compared against pre-dequantized inputs), and the
    streaming kernel agrees with the jnp oracle on int8 inputs to
    fp32 softmax tolerance — across a multi-k-block shape so the
    scale rows stream per block."""
    rng = np.random.RandomState(3)
    b, t, h, d = 2, 160, 2, 16   # > one 128-lane k-block after pad
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32)
    kq, ks = quantize_kv(jnp.asarray(rng.randn(b, t, h, d),
                                     jnp.float32))
    vq, vs = quantize_kv(jnp.asarray(rng.randn(b, t, h, d),
                                     jnp.float32))
    bias = np.zeros((b, t), np.float32)
    bias[1, 150:] = -1e30        # masked tail crossing the last block
    bias = jnp.asarray(bias)
    kd = dequantize_kv(kq, ks, q.dtype)
    vd = dequantize_kv(vq, vs, q.dtype)

    oracle = cached_attention(q, kq, vq, kv_bias=bias, k_scale=ks,
                              v_scale=vs, use_pallas=False)
    oracle_pre = cached_attention(q, kd, vd, kv_bias=bias,
                                  use_pallas=False)
    assert np.array_equal(np.asarray(oracle), np.asarray(oracle_pre))

    kern = cached_attention(q, kq, vq, kv_bias=bias, k_scale=ks,
                            v_scale=vs, use_pallas=True,
                            interpret=True, block_k=128)
    kern_pre = cached_attention(q, kd, vd, kv_bias=bias,
                                use_pallas=True, interpret=True,
                                block_k=128)
    assert np.array_equal(np.asarray(kern), np.asarray(kern_pre))
    np.testing.assert_allclose(np.asarray(kern), np.asarray(oracle),
                               rtol=2e-5, atol=2e-6)

    # the chunk op (the verify/chunk-prefill read path) dequantizes
    # by the same rule
    c = 4
    qc = jnp.asarray(rng.randn(b, c, h, d), jnp.float32)
    kq2, ks2 = quantize_kv(jnp.asarray(rng.randn(b, t + c, h, d),
                                       jnp.float32))
    vq2, vs2 = quantize_kv(jnp.asarray(rng.randn(b, t + c, h, d),
                                       jnp.float32))
    got = chunk_cached_attention(qc, kq2, vq2, bias, k_scale=ks2,
                                 v_scale=vs2)
    want = chunk_cached_attention(
        qc, dequantize_kv(kq2, ks2, qc.dtype),
        dequantize_kv(vq2, vs2, qc.dtype), bias)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_scale_arg_validation():
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 1, 2, 8), jnp.float32)
    kq, ks = quantize_kv(jnp.asarray(rng.randn(1, 8, 2, 8),
                                     jnp.float32))
    with pytest.raises(ValueError, match="together"):
        cached_attention(q, kq, kq, k_scale=ks)
    with pytest.raises(ValueError, match="scales"):
        cached_attention(q, kq, kq, k_scale=ks[:, :4],
                         v_scale=ks[:, :4])


# -- config / accounting satellites -----------------------------------------

def test_resolve_cache_dtype_rejects_integer_dtypes():
    """An int dtype passed as the cache COMPUTE dtype would silently
    build a garbage pool; it must fail loudly, naming the quantize=
    knob that actually turns on int8 storage."""
    for bad in (jnp.int8, jnp.int32, np.int8, "int8"):
        with pytest.raises(TypeError, match="quantize='int8'"):
            resolve_cache_dtype(bad)
    with pytest.raises(TypeError, match="quantize='int8'"):
        KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                      num_blocks=4, dtype=jnp.int8)
    # the float path is untouched
    assert resolve_cache_dtype(jnp.bfloat16) == jnp.dtype(jnp.bfloat16)


def test_resolve_kv_quant_values():
    assert resolve_kv_quant(None) is None
    assert resolve_kv_quant("") is None
    assert resolve_kv_quant("0") is None
    assert resolve_kv_quant("off") is None
    assert resolve_kv_quant("int8") == "int8"
    assert resolve_kv_quant("1") == "int8"
    with pytest.raises(ValueError, match="int8"):
        resolve_kv_quant("fp4")


def test_config_bytes_include_scale_sidecar():
    """``bytes_per_block`` / ``bytes()`` price the sidecar: occupancy
    math and the fixed-pool-bytes bench arms divide by the TRUE cost
    of a block, and at head_dim 64 the bf16->int8 headroom clears the
    1.8x floor net of scales."""
    kw = dict(num_layers=2, num_heads=4, head_dim=64, num_blocks=10,
              block_size=16)
    plain = KVCacheConfig(dtype=jnp.bfloat16, **kw)
    quant = KVCacheConfig(dtype=jnp.bfloat16, quantize="int8", **kw)
    # payload: 2 sides * L * bs * H * D * itemsize
    assert plain.bytes_per_block == 2 * 2 * 16 * 4 * 64 * 2
    assert quant.bytes_per_block == \
        2 * 2 * 16 * 4 * 64 * 1 + 2 * 2 * 16 * 4 * 4
    assert plain.bytes() == 10 * plain.bytes_per_block
    assert quant.bytes() == 10 * quant.bytes_per_block
    assert plain.bytes_per_block / quant.bytes_per_block >= 1.8
    assert quant.storage_dtype() == jnp.dtype(jnp.int8)
    assert quant.resolved_dtype() == jnp.dtype(jnp.bfloat16)
    with pytest.raises(ValueError, match="quantize"):
        KVCacheConfig(quantize="fp8", **kw)


def test_quant_memory_stats_and_q8_program_keys(tiny):
    """The pinned memory keys under quantization — storage dtype
    int8, quantize mode, sidecar-inclusive bytes — and the q8-tagged
    program accounting keys the compile audits bound quant-on traces
    by."""
    cfg, params = tiny
    srv = _server(cfg, params, max_batch_size=2, max_context=64,
                  block_size=8)
    srv.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
    st = srv.stats()
    mem = st["memory"]
    assert mem["cache_dtype"] == "int8"
    assert mem["quantize"] == "int8"
    assert mem["compute_dtype"] == "float32"
    assert mem["pool_bytes"] == \
        srv.engine.cache_cfg.num_blocks * mem["bytes_per_block"]
    assert mem["pool_bytes_per_device"] == mem["pool_bytes"]
    # every quant-on launch accounts under a q8-tagged key
    keys = set(st["programs"]["by_program"])
    assert keys and all(k.endswith("q8]") for k in keys), keys
    # the same traffic quant-OFF uses the untagged keys
    srv0 = InferenceServer(cfg, params, max_batch_size=2,
                           max_context=64, block_size=8,
                           cache_dtype=jnp.float32)
    srv0.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
    keys0 = set(srv0.stats()["programs"]["by_program"])
    assert not any(k.endswith("q8]") for k in keys0), keys0
    # compile audits hold unchanged under quantization (speculation
    # may route every decode iteration through verify, so decode can
    # legitimately sit at 0 — the bound is what must not grow)
    pre, dec = srv.engine.compile_counts()
    assert dec <= 1
    assert srv.engine.verify_compiles() <= 1
    assert pre == 1          # one chunk width, one chunk program


def test_env_twin_turns_quant_on(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setenv("APEX_TPU_KV_QUANT", "int8")
    srv = InferenceServer(cfg, params, max_batch_size=2,
                          max_context=64, block_size=8,
                          cache_dtype=jnp.float32)
    assert srv.engine.quantized
    assert srv.stats()["memory"]["quantize"] == "int8"
    # a PROVIDED kwarg wins over the env in both directions: "int8"
    # beats an env "off", and "off" beats an env "int8" (the bench's
    # legacy arms pin "off" so APEX_TPU_KV_QUANT cannot silently
    # quantize a full-width baseline; None = defer to the env)
    monkeypatch.setenv("APEX_TPU_KV_QUANT", "off")
    srv2 = InferenceServer(cfg, params, max_batch_size=2,
                           max_context=64, block_size=8,
                           cache_dtype=jnp.float32, kv_quant="int8")
    assert srv2.engine.quantized
    monkeypatch.setenv("APEX_TPU_KV_QUANT", "int8")
    srv3 = InferenceServer(cfg, params, max_batch_size=2,
                           max_context=64, block_size=8,
                           cache_dtype=jnp.float32, kv_quant="off")
    assert not srv3.engine.quantized
    monkeypatch.setenv("APEX_TPU_KV_QUANT", "fp4")
    with pytest.raises(ValueError, match="int8"):
        InferenceServer(cfg, params, max_batch_size=2,
                        max_context=64, block_size=8)


# -- the decode-parity tolerance oracle -------------------------------------

def test_decode_parity_tolerance_oracle_64_tokens(tiny):
    """The quality gate: 64-token greedy generations quant-on vs
    quant-off on the standard tiny-GPT config, held to a pinned
    token-agreement budget.  int8 per-token per-head absmax is
    accurate enough that the tiny model agrees perfectly today
    (measured 64/64 on every prompt); the pinned floor leaves margin
    because the oracle is a TOLERANCE gate by design — see the
    BENCH_NOTES kv-quant decision table for the accept/reject
    ladder."""
    cfg, params = tiny
    rng = np.random.RandomState(11)
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6],
               list(rng.randint(0, VOCAB, size=12)),
               list(rng.randint(0, VOCAB, size=5))]
    kw = dict(max_batch_size=3, max_context=128, block_size=8)
    on = _audited_generate(_server(cfg, params, **kw), prompts, 64)
    off = _audited_generate(
        InferenceServer(cfg, params, cache_dtype=jnp.float32, **kw),
        prompts, 64)
    agree = [_lcp(a, b) for a, b in zip(on, off)]
    assert all(len(o) == 64 for o in on)
    # the budget: >= 75% agreeing prefix per request, on average
    assert sum(agree) / (64 * len(prompts)) >= 0.75, \
        f"quant-on diverged past budget: agreeing prefixes {agree}"


# -- bit-stability: quant-on vs quant-on under every lifecycle path ---------

def test_quant_bit_stable_composed_stress(tiny):
    """The tentpole's stability bar: the SAME quant-on computation
    under a pool small enough to force preemption AND prefix-cache
    eviction, a whole-context COW hit, chunked prefill, speculation
    rollback, and the pipelined loop must produce tokens identical to
    a roomy, unstressed quant-on server — quantized blocks survive
    every block-lifecycle path bit-consistently."""
    cfg, params = tiny
    rng = np.random.RandomState(7)
    shared = list(rng.randint(0, VOCAB, size=12))
    rep = [1, 2, 3, 1, 2, 3, 1, 2] * 2
    # wave 1 overflows the pool (preemption, and eviction takes rep's
    # blocks with the rest); wave 2 is rep alone, so that it finishes
    # with nothing after it to evict its blocks; wave 3 re-sends rep
    # (the whole-context COW hit) beside a shared-prefix sibling
    waves = [[rep,
              shared + [5, 6, 7, 8],
              list(rng.randint(0, VOCAB, size=8))],
             [list(rep)],
             [list(rep),
              shared + [9, 8, 7, 6]]]
    stress_kw = dict(max_batch_size=3, max_context=64, block_size=4,
                     num_blocks=21, prefill_chunk=8)
    srv = _server(cfg, params, **stress_kw)
    got = [o for w in waves for o in _audited_generate(srv, w, 20)]
    roomy = _server(cfg, params, max_batch_size=3, max_context=64,
                    block_size=4)
    want = [o for w in waves for o in _audited_generate(roomy, w, 20)]
    assert got == want, "quant-on tokens moved under composed stress"
    st = srv.stats()
    # every composed mechanism actually fired on the stressed server
    assert st["preemptions"] >= 1
    assert st["prefix_evicted_blocks"] >= 1
    assert st["prefix_cow_blocks"] >= 1
    assert st["prefill_chunks"] >= 1
    assert st["speculation"]["accepted_tokens"] >= 1
    assert st["pipeline"]["launches"] >= 1
    assert st["memory"]["quantize"] == "int8"


def test_quant_pool_at_equal_bytes_churns_no_more(tiny):
    """What the headroom buys: the bf16 pool's byte budget re-spent on
    int8 blocks with their scale sidecar holds more blocks (the live
    arrays within the budget, not only the price list), and the
    schedule that preempts and evicts on the bf16 pool does neither
    more often on the int8 one."""
    cfg, params = tiny
    rng = np.random.RandomState(7)
    shared = list(rng.randint(0, VOCAB, size=12))
    waves = [[[1, 2, 3, 1, 2, 3, 1, 2] * 2, shared + [5, 6, 7, 8],
              list(rng.randint(0, VOCAB, size=8))],
             [shared + [9, 8, 7, 6], list(rng.randint(0, VOCAB, size=8))]]
    kw = dict(max_batch_size=3, max_context=64, block_size=4,
              prefill_chunk=8, cache_dtype=jnp.bfloat16)
    base = _server(cfg, params, kv_quant="off", num_blocks=21, **kw)
    budget = base.stats()["memory"]["pool_bytes"]
    per_block = _server(cfg, params, num_blocks=2,
                        **kw).stats()["memory"]["bytes_per_block"]
    quant = _server(cfg, params, num_blocks=budget // per_block, **kw)
    churn = []
    for srv in (base, quant):
        for w in waves:
            _audited_generate(srv, w, 20)
        st = srv.stats()
        churn.append((st["preemptions"], st["prefix_evicted_blocks"]))
    assert quant.stats()["memory"]["pool_bytes"] <= budget
    assert quant.stats()["memory"]["blocks_usable"] \
        > base.stats()["memory"]["blocks_usable"]
    assert churn[0][0] >= 1 and churn[0][1] >= 1, churn   # not vacuous
    assert churn[1][0] <= churn[0][0] and churn[1][1] <= churn[0][1], churn


@pytest.mark.slow
def test_quant_pipeline_matches_sync_and_spec_off(tiny):
    """Quant-on output is identical across the pipelined loop, the
    synchronous loop, and speculation on/off — the quantized grid is
    a property of the VALUES, not of which program read them."""
    cfg, params = tiny
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8],
               [1, 2, 3, 1, 2, 3, 1, 2, 1, 2, 3, 1]]
    kw = dict(max_batch_size=3, max_context=64, block_size=8)
    base = _audited_generate(_server(cfg, params, **kw), prompts, 24)
    sync = _audited_generate(
        _server(cfg, params, enable_pipeline=False, **kw),
        prompts, 24)
    nospec = _audited_generate(
        _server(cfg, params, enable_speculation=False, **kw),
        prompts, 24)
    assert base == sync == nospec


@pytest.mark.parametrize(
    "tp",
    [pytest.param(1, marks=pytest.mark.slow), 2,
     pytest.param(4, marks=pytest.mark.slow)])
def test_quant_tp_parity(tiny, tp):
    """Quantized pool + scale sidecar under tensor parallelism: the
    head-sharded layout carries each head's scales on its own shard,
    and the sharded quant-on server is bit-identical to the unsharded
    quant-on server (tp=1 pins the mesh-of-one lowering too)."""
    cfg, params = tiny
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    kw = dict(max_batch_size=2, max_context=128, block_size=8)
    want = _audited_generate(_server(cfg, params, **kw), [prompt], 32)
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("model",))
    srv = _server(cfg, params, mesh=mesh, **kw)
    got = _audited_generate(srv, [prompt], 32)
    assert got == want, f"tp={tp} quant-on diverged"
    mi = srv.engine.memory_info()
    assert mi["pool_bytes_per_device"] * tp == mi["pool_bytes"]
    # the sidecar is genuinely head-sharded: each device holds H/tp
    # heads' scale rows
    ksc, _ = scale_sidecar(srv.engine.cache)
    shard = ksc.sharding.shard_shape(ksc.shape)
    assert shard[-1] == cfg.num_attention_heads // tp


@pytest.mark.slow
def test_quant_bit_stable_mini_soak(tiny):
    """A 160-iteration seeded mini chaos soak with quantization ON in
    both the soaked server and the replay oracle: the bit-exact-replay
    invariant must hold with int8 blocks flowing through every fault
    class (the build-matrix ``kv_quant`` axis runs the full 800)."""
    import time as _time

    from apex_tpu.resilience import CircuitBreaker
    from apex_tpu.resilience.chaos import ChaosConfig, run_soak

    cfg, params = tiny

    def make_server(clock):
        return _server(cfg, params, max_batch_size=4, max_context=64,
                       block_size=4, num_blocks=40, max_waiting=8,
                       clock=clock,
                       breaker=CircuitBreaker(failure_threshold=3,
                                              recovery_time=25.0,
                                              clock=clock))

    def make_replay(clock):
        return _server(cfg, params, max_batch_size=4, max_context=64,
                       block_size=4, clock=clock)

    report = run_soak(make_server,
                      ChaosConfig(iters=160, vocab=VOCAB), seed=0,
                      make_replay=make_replay)
    assert report["submitted"] >= 1
    assert report["bit_exact_checked"] >= 1
