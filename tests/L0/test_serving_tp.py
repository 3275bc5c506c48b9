"""Tensor-parallel sharded serving: GSPMD decode over a device mesh
must be a PLACEMENT of the single-chip engine, never a different
computation.

The load-bearing oracle is bit-exact greedy parity between a
``mesh=``-sharded :class:`InferenceServer` (params under
``gpt_tp_rules``, KV pool head-sharded, all programs lowered through
GSPMD, the sampled twins on the fused ``ops.vocab_parallel_sample``
path) and the unsharded engine over 64 generated tokens — under plain
decode, prefix-cache COW hits, forced preemption, forced eviction,
chunked prefill, speculation, and the pipelined loop, with the
scheduler ``audit()`` passing every step.  Tie-sensitive argmaxes
resolve by the documented lowest-global-id rule on both paths, so ANY
divergence means the sharded lowering changed a logit past argmax
resolution or a scheduling decision — exactly the bug classes this
file exists to catch.

Runs on the emulated 8-device CPU mesh the whole distributed tier uses
(``tests/conftest.py`` forces ``--xla_force_host_platform_device_count
=8``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from apex_tpu import models
from apex_tpu.serving import InferenceServer

pytestmark = pytest.mark.serving

# divides tp 2 AND 4, so the tied wte actually shards its vocab dim
# (gpt_tp_rules) and the fused vocab-parallel argmax path is exercised
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

    @jax.jit
    def oracle_step(ids, mask):
        return m.apply({"params": params}, ids, attention_mask=mask)

    return cfg, params, oracle_step


def _mesh(tp):
    return Mesh(np.asarray(jax.devices()[:tp]), ("model",))


def _server(cfg, params, mesh=None, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return InferenceServer(cfg, params, mesh=mesh, **kw)


def _audited_generate(server, prompts, n, **kw):
    reqs = [server.submit(p, n, **kw) for p in prompts]
    while server.scheduler.has_work:
        server.step()
        server.scheduler.audit()
    return [list(r.generated) for r in reqs]


def _assert_parity(got, want, what):
    for i, (a, b) in enumerate(zip(got, want)):
        assert a == b, (f"{what}: request {i} diverged: "
                        f"sharded={a} unsharded={b}")


# -- the headline oracle ----------------------------------------------------

@pytest.mark.parametrize("tp", [2, 4])
def test_tp_matches_unsharded_and_oracle_64_tokens(tiny, tp):
    """The acceptance bar: 64 greedy tokens, token-for-token, tp ∈
    {2, 4} vs the unsharded engine AND the full-recompute training
    forward — speculation and the pipelined loop on (the defaults),
    audit every step."""
    cfg, params, oracle_step = tiny
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    kw = dict(max_batch_size=2, max_context=128, block_size=8)
    got = _audited_generate(_server(cfg, params, _mesh(tp), **kw),
                            [prompt], 64)[0]
    want = _audited_generate(_server(cfg, params, None, **kw),
                             [prompt], 64)[0]
    assert len(got) == 64
    _assert_parity([got], [want], f"tp={tp} 64-token")
    # and against the training-forward oracle (full recompute)
    toks = list(prompt)
    ids = np.zeros((1, 128), np.int32)
    mask = np.zeros((1, 128), np.int32)
    for _ in range(64):
        ln = len(toks)
        ids[0, :ln] = toks
        mask[0, :ln] = 1
        logits = oracle_step(jnp.asarray(ids), jnp.asarray(mask))
        toks.append(int(np.argmax(np.asarray(logits[0, ln - 1]))))
    assert got == toks[len(prompt):]


def test_tp_parity_composed_stress(tiny):
    """The composed scenario the tentpole promises: a pool small
    enough to force preemption AND prefix-cache eviction, chunked
    prefill on a small chunk, repetitive prompts so speculation
    accepts drafts, a repeated whole prompt so a COW hit fires — all
    on the pipelined loop, audited every step, bit-identical to the
    unsharded server under the identical configuration."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(7)
    shared = list(rng.randint(0, VOCAB, size=12))
    rep = [1, 2, 3, 1, 2, 3, 1, 2] * 2
    # wave 1 populates the prefix cache and overflows the pool
    # (eviction takes rep's blocks with the rest); wave 2 is rep
    # alone, so that it finishes with nothing after it to evict its
    # blocks; wave 3 re-sends the whole rep prompt (whole-context COW
    # hit) plus a shared-prefix sibling (partial hit)
    waves = [[rep,                            # speculation fodder
              shared + [5, 6, 7, 8],          # prefix-cache feeder
              list(rng.randint(0, VOCAB, size=8))],
             [list(rep)],
             [list(rep),                      # whole-context COW hit
              shared + [9, 8, 7, 6]]]         # prefix hit
    kw = dict(max_batch_size=3, max_context=64, block_size=4,
              num_blocks=21, prefill_chunk=8)
    srv = _server(cfg, params, _mesh(2), **kw)
    got = [o for w in waves for o in _audited_generate(srv, w, 20)]
    base = _server(cfg, params, None, **kw)
    want = [o for w in waves for o in _audited_generate(base, w, 20)]
    _assert_parity(got, want, "composed-stress")
    st = srv.stats()
    # every composed mechanism actually fired on the SHARDED server
    assert st["preemptions"] >= 1
    assert st["prefix_hit_requests"] >= 1
    assert st["prefix_cow_blocks"] >= 1
    assert st["prefill_chunks"] >= 1
    assert st["speculation"]["accepted_tokens"] >= 1
    assert st["pipeline"]["launches"] >= 1
    assert st["sharding"]["enabled"] and st["sharding"]["tp"] == 2


def test_tp_parity_under_forced_preemption(tiny):
    """A pool too small for the running set: the sharded scheduler
    must preempt the same victims at the same points (block tables
    and the allocator are replicated host state — sharding must not
    perturb them)."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6],
               [2, 7, 1, 8, 2, 8, 1, 8],
               [9, 9, 8, 7, 6, 5, 4, 3]]
    kw = dict(max_batch_size=3, max_context=64, block_size=4,
              num_blocks=10)
    srv = _server(cfg, params, _mesh(2), **kw)
    got = _audited_generate(srv, prompts, 24)
    want = _audited_generate(_server(cfg, params, None, **kw),
                             prompts, 24)
    _assert_parity(got, want, "forced-preemption")
    assert srv.stats()["preemptions"] >= 1


def test_tp_parity_under_forced_prefix_eviction(tiny):
    """Sequential shared-prefix traffic on a pool too small to keep
    every cache hold resident: LRU eviction must fire identically
    sharded."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(3)
    shared = list(rng.randint(0, VOCAB, size=12))
    prompts = [shared + list(rng.randint(0, VOCAB, size=4))
               for _ in range(4)]
    kw = dict(max_batch_size=2, max_context=64, block_size=4,
              num_blocks=14)
    srv = _server(cfg, params, _mesh(2), **kw)
    got = _audited_generate(srv, prompts, 16)
    want = _audited_generate(_server(cfg, params, None, **kw),
                             prompts, 16)
    _assert_parity(got, want, "forced-eviction")
    assert srv.stats()["prefix_evicted_blocks"] >= 1


def test_tp_parity_synchronous_logits_path(tiny):
    """Pipeline off: the logits programs run instead of the sampled
    twins, so GSPMD all-gathers the vocab-sharded logits for the host
    sampler — same tokens, by construction."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8]]
    kw = dict(max_batch_size=2, max_context=64, block_size=8,
              enable_pipeline=False, enable_speculation=False)
    got = _audited_generate(_server(cfg, params, _mesh(2), **kw),
                            prompts, 16)
    want = _audited_generate(_server(cfg, params, None, **kw),
                             prompts, 16)
    _assert_parity(got, want, "synchronous-logits")


def test_tp_compile_counts_one_program_per_logical_shape(tiny):
    """Sharding must not multiply compiles: the audit bounds hold
    unchanged (GSPMD lowers ONE program per logical shape — shards
    are inside the program, not more programs), and every mesh-lowered
    trace is tallied by ``collective_programs``."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, VOCAB, size=n))
               for n in (3, 9, 14, 17, 25, 31)]
    srv = _server(cfg, params, _mesh(2), max_batch_size=3,
                  max_context=64, block_size=8,
                  enable_speculation=False)
    srv.generate(prompts, max_new_tokens=12)
    pre, dec = srv.engine.compile_counts()
    assert dec == 1, f"decode recompiled: {dec} programs"
    assert pre == 1, f"{pre} chunk programs for one chunk width"
    assert srv.engine.verify_compiles() == 0
    assert srv.engine.collective_programs() == \
        pre + dec + srv.engine.verify_compiles() \
        + srv.engine._copy_jit._cache_size()


# -- stats / observability --------------------------------------------------

def test_sharding_stats_block_pinned(tiny):
    """The pinned ``stats()["sharding"]`` block — dashboards and the
    tp bench key on these literally — and the per-logical-program
    accounting contract: one ``serving_program_*`` entry per program,
    never per shard."""
    cfg, params, _ = tiny
    srv = _server(cfg, params, _mesh(2), max_batch_size=2,
                  max_context=64, block_size=8)
    srv.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
    st = srv.stats()
    sh = st["sharding"]
    assert set(sh) == {"enabled", "tp", "axis", "devices", "mesh",
                       "kv_pool_bytes_per_device",
                       "collective_programs"}
    assert sh["enabled"] is True and sh["tp"] == 2
    assert sh["axis"] == "model" and sh["devices"] == 2
    assert sh["mesh"] == {"model": 2}
    assert sh["kv_pool_bytes_per_device"] * 2 == \
        st["memory"]["pool_bytes"]
    assert sh["collective_programs"] >= 2
    # program accounting stays LOGICAL: the sharded server's program
    # keys are exactly the unsharded server's for identical traffic
    srv1 = _server(cfg, params, None, max_batch_size=2,
                   max_context=64, block_size=8)
    srv1.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
    sharded_keys = set(st["programs"]["by_program"])
    unsharded_keys = set(srv1.stats()["programs"]["by_program"])
    assert sharded_keys == unsharded_keys
    sh1 = srv1.stats()["sharding"]
    assert sh1["enabled"] is False and sh1["tp"] == 1
    assert sh1["mesh"] is None and sh1["devices"] == 1
    assert sh1["kv_pool_bytes_per_device"] == \
        srv1.stats()["memory"]["pool_bytes"]
    assert sh1["collective_programs"] == 0


def test_memory_info_reports_actual_per_device_shard(tiny):
    """The per-chip HBM fix: ``memory_info()`` /
    ``stats()["memory"]`` report the ACTUAL per-device bytes from the
    live shard's shape and dtype — the logical pool size would
    overstate per-chip HBM by tp× (and by 2× for a bf16 cache sized
    off an fp32 assumption)."""
    cfg, params, _ = tiny
    for tp, mesh in ((1, None), (2, _mesh(2)), (4, _mesh(4))):
        srv = _server(cfg, params, mesh, max_batch_size=2,
                      max_context=64, block_size=8)
        info = srv.engine.memory_info()
        assert info["pool_bytes_per_device"] * tp == \
            info["pool_bytes"], (tp, info)
        mem = srv.stats()["memory"]
        assert mem["pool_bytes_per_device"] == \
            info["pool_bytes_per_device"]
        # dtype comes from the live array, not an assumption: a bf16
        # pool is half the fp32 one, per device too
        half = InferenceServer(cfg, params, mesh=mesh,
                               max_batch_size=2, max_context=64,
                               block_size=8,
                               cache_dtype=jnp.bfloat16)
        assert half.engine.memory_info()["pool_bytes_per_device"] \
            * 2 == info["pool_bytes_per_device"], tp
        assert half.engine.memory_info()["cache_dtype"] == "bfloat16"


# -- configuration errors ---------------------------------------------------

def test_tp_rejects_indivisible_heads_and_missing_axis(tiny):
    cfg, params, _ = tiny
    bad = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=30, num_hidden_layers=1,
        num_attention_heads=3, intermediate_size=32,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(bad)
    bad_params = m.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 4), jnp.int32))["params"]
    with pytest.raises(ValueError, match="num_attention_heads"):
        InferenceServer(bad, bad_params, mesh=_mesh(2),
                        max_batch_size=2, block_size=8)
    with pytest.raises(ValueError, match="tp_axis"):
        InferenceServer(cfg, params, mesh=_mesh(2), tp_axis="tp",
                        max_batch_size=2, block_size=8)
