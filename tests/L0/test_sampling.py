"""On-device stochastic sampling (``docs/serving.md``, "Stochastic
sampling").

Three pillars, each an explicit contract:

- **distribution exactness**: :func:`ops.sample_tokens` draws from
  exactly ``softmax(processed logits)`` — fixed-key frequency oracles
  against numpy-computed targets (temperature scaling, top-k mask
  exactness, top-p boundary inclusion), plus the rejection-sampling
  coupling (accept prob == p(draft), residual distribution exact);
- **greedy bit-parity**: the default ``SamplingParams()`` is
  byte-identical to the historical argmax path at every level (the
  op, mixed stochastic launches, the full server);
- **counter-key determinism**: streams are pure functions of
  ``(prompt, params, seed)`` — byte-identical across replay,
  speculation on/off, pipelining on/off, forced preemption and
  prefix-cache eviction, and tensor-parallel sharding (the Gumbel-max
  coupling makes the fast paths invisible to outputs, which is what
  lets stochastic traffic keep them ON).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.ops.sampling import (
    SamplingParams,
    greedy_argmax,
    processed_logits,
    sample_tokens_host,
)
from apex_tpu.serving import InferenceServer, greedy_sample

pytestmark = pytest.mark.serving

VOCAB = 61


# -- helpers ---------------------------------------------------------------

def _draw(logits_row, n, *, temperature=1.0, top_k=0, top_p=1.0,
          seed=0, pos0=0):
    """n i.i.d.-across-positions draws from one logits row via the
    real sampler (each position is an independent counter key)."""
    v = len(logits_row)
    lg = np.broadcast_to(np.asarray(logits_row, np.float32),
                         (n, v)).copy()
    ids, fin = sample_tokens_host(
        lg,
        np.full((n,), temperature, np.float32),
        np.full((n,), top_k, np.int32),
        np.full((n,), top_p, np.float32),
        np.full((n,), seed, np.int32),
        (pos0 + np.arange(n)).astype(np.int32))
    assert bool(np.all(np.asarray(fin)))
    return np.asarray(ids)


def _chi2(freq_counts, probs):
    """Pearson chi-square statistic of observed counts vs target
    probabilities (zero-prob cells must be unobserved)."""
    n = freq_counts.sum()
    stat = 0.0
    for o, p in zip(freq_counts, probs):
        if p == 0.0:
            assert o == 0, "sampled a zero-probability token"
            continue
        e = n * p
        stat += (o - e) ** 2 / e
    return stat


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


def _server(cfg, params, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", 4)
    return InferenceServer(cfg, params, **kw)


def _prompts_and_params(n=4):
    rng = np.random.RandomState(0)
    prompts = [[int(x) for x in rng.randint(0, VOCAB,
                                            size=rng.randint(4, 12))]
               for _ in range(n - 1)]
    prompts.append([7, 8, 9] * 5)       # repetitive: drafts fire
    samp = [SamplingParams(temperature=0.8, top_p=0.95, seed=i + 1)
            for i in range(len(prompts))]
    return prompts, samp


# -- SamplingParams (validation + classes) ---------------------------------

def test_sampling_params_validation_messages():
    with pytest.raises(ValueError, match="temperature must be >= 0"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_k must be >= 1"):
        SamplingParams(top_k=0)
    with pytest.raises(ValueError, match=r"top_p must be in \(0, 1\]"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match=r"top_p must be in \(0, 1\]"):
        SamplingParams(top_p=1.5)


def test_sampling_params_defaults_and_classes():
    d = SamplingParams()
    assert d.is_greedy and d.klass == "greedy"
    assert SamplingParams(temperature=1.0).klass == "temperature"
    assert SamplingParams(temperature=1.0, top_k=5).klass == "top_k"
    assert SamplingParams(temperature=1.0, top_p=0.9).klass == "top_p"
    assert SamplingParams(temperature=1.0, top_k=5,
                          top_p=0.9).klass == "top_k_top_p"
    # temperature 0 is greedy regardless of filters
    assert SamplingParams(top_k=5, top_p=0.5).is_greedy


# -- the op: greedy lane bit-parity ----------------------------------------

def test_greedy_lane_bit_exact_vs_argmax():
    """temperature-0 rows of the stochastic sampler must be
    byte-identical to ``greedy_argmax``/``np.argmax`` — ties (lowest
    id) included — for fp32 and bf16 logits."""
    rng = np.random.RandomState(1)
    for dtype in (jnp.float32, jnp.bfloat16):
        lg = jnp.asarray(rng.randn(32, 40), dtype)
        # manufacture exact ties
        lg = lg.at[3, 7].set(lg[3, 20]).at[9, 0].set(lg[9, 39])
        b = lg.shape[0]
        ids, fin = sample_tokens_host(
            lg, np.zeros((b,), np.float32), np.zeros((b,), np.int32),
            np.ones((b,), np.float32), np.zeros((b,), np.int32),
            np.arange(b, dtype=np.int32))
        want = np.argmax(np.asarray(lg, np.float32), axis=-1)
        assert np.array_equal(np.asarray(ids), want)
        assert np.asarray(fin).all()


def test_nonfinite_rows_flagged():
    lg = np.zeros((3, 8), np.float32)
    lg[1, 2] = np.nan
    lg[2, 5] = np.inf
    _ids, fin = sample_tokens_host(
        lg, np.full((3,), 1.0, np.float32), np.zeros((3,), np.int32),
        np.ones((3,), np.float32), np.zeros((3,), np.int32),
        np.arange(3, dtype=np.int32))
    assert np.asarray(fin).tolist() == [True, False, False]


# -- the op: fixed-key distribution oracles vs numpy -----------------------

def test_temperature_scaling_distribution():
    """Sampled frequencies match numpy-computed
    ``softmax(logits / T)`` under a chi-square bound, and temperature
    actually reshapes the distribution."""
    lg = np.array([2.0, 1.0, 0.3, -0.5, -1.2], np.float32)
    n = 12000
    for t in (0.5, 1.0, 2.0):
        ids = _draw(lg, n, temperature=t, seed=17)
        counts = np.bincount(ids, minlength=5)
        p = np.exp(lg / t)
        p /= p.sum()
        # df=4, p~1e-3 critical value 18.5 — generous but real
        assert _chi2(counts, p) < 18.5, \
            (t, counts / n, p)


def test_top_k_mask_exactness():
    """Only the top-k ids can ever be sampled; ties AT the k-th value
    are all kept (the documented value-threshold rule); the kept
    distribution is the renormalized top-k softmax."""
    lg = np.array([1.5, 3.0, 0.0, 2.0, -1.0, 0.5], np.float32)
    ids = _draw(lg, 8000, top_k=3, seed=5)
    assert set(ids.tolist()) == {1, 3, 0}     # the top-3 ids, nothing else
    p = np.exp(lg)
    p[[2, 4, 5]] = 0.0
    p /= p.sum()
    assert _chi2(np.bincount(ids, minlength=6), p) < 18.5
    # exact tie at the boundary: both tied ids stay sampleable
    lg_tie = np.array([3.0, 2.0, 2.0, -5.0], np.float32)
    ids = _draw(lg_tie, 4000, top_k=2, seed=6)
    assert set(ids.tolist()) == {0, 1, 2}


def test_top_p_boundary_inclusion():
    """The token whose cumulative probability CROSSES top_p is
    included; everything past it is masked; the kept distribution is
    the renormalized nucleus."""
    # softmax ~ [0.643, 0.237, 0.087, 0.032] (+ tail)
    lg = np.array([2.0, 1.0, 0.0, -1.0], np.float32)
    p_full = np.exp(lg) / np.exp(lg).sum()
    # top_p = 0.8: cum [0.64, 0.88, ...] -> boundary token 1 INCLUDED
    ids = _draw(lg, 8000, top_p=0.8, seed=9)
    assert set(ids.tolist()) == {0, 1}
    p = p_full.copy()
    p[2:] = 0.0
    p /= p.sum()
    assert _chi2(np.bincount(ids, minlength=4), p) < 18.5
    # top_p below the top token's prob: argmax only
    ids = _draw(lg, 1000, top_p=0.1, seed=10)
    assert set(ids.tolist()) == {0}
    # top_p = 1.0 keeps everything (never truncates an underflowed
    # tail)
    ids = _draw(lg, 12000, top_p=1.0, seed=11)
    assert set(ids.tolist()) == {0, 1, 2, 3}


def test_counter_key_determinism():
    """Same (seed, position) -> the same token, always; distinct
    positions/seeds decorrelate."""
    lg = np.array([0.5, 0.4, 0.3, 0.2, 0.1], np.float32)
    a = _draw(lg, 64, seed=3)
    b = _draw(lg, 64, seed=3)
    assert np.array_equal(a, b)
    c = _draw(lg, 64, seed=4)
    assert not np.array_equal(a, c)
    # a single position re-drawn is a constant
    d = _draw(lg, 50, seed=3, pos0=7)[0:1]
    for _ in range(3):
        assert _draw(lg, 1, seed=3, pos0=7)[0] == d[0]


def test_rejection_sampling_exactness():
    """The speculative acceptance rule (accept draft iff it equals
    the column's sample — the Gumbel-max coupling) realizes rejection
    sampling's exact probabilities for a delta draft: accept rate ==
    p(draft), and the emitted token conditional on rejection follows
    the normalized residual p(x)/(1-p(d)) — chi-square on a small
    vocab."""
    lg = np.array([1.2, 0.6, 0.0, -0.6, -1.2, 0.3], np.float32)
    p = np.exp(lg) / np.exp(lg).sum()
    d = 1                                    # the drafted token
    n = 15000
    s = _draw(lg, n, temperature=1.0, seed=23)
    accept = s == d
    rate = accept.mean()
    se = np.sqrt(p[d] * (1 - p[d]) / n)
    assert abs(rate - p[d]) < 5 * se, (rate, p[d])
    resampled = s[~accept]
    residual = p.copy()
    residual[d] = 0.0
    residual /= residual.sum()
    assert _chi2(np.bincount(resampled, minlength=6), residual) < 20.5


# -- the op: thresholds by selection against the sort-based oracle ----------

def _oracle_thresholds(scaled, top_k, top_p):
    """What ``processed_logits`` computed before it selected: one row
    sorted, the k-th value by index, the nucleus boundary from a
    cumulative sum, here in float64.  Returns ``(kth, pths)``:
    ``pths`` holds the boundary value and, where the inclusive mass on
    either side of it lies within 1e-5 of ``top_p`` (float32 sums of a
    vocabulary cannot tell those apart), the neighbouring distinct
    value on that side too."""
    v = len(scaled)
    desc = -np.sort(-scaled.astype(np.float64))
    kth = desc[min(top_k, v) - 1] if top_k > 0 else -np.inf
    if top_p >= 1.0:
        return kth, [-np.inf]
    e = np.exp(desc - desc[0])
    cum = np.cumsum(e) / e.sum()
    bnd = min(int(np.sum(cum < top_p)), v - 1)
    pths = [desc[bnd]]
    above = desc[desc > desc[bnd]]          # what a higher boundary keeps
    if len(above) and top_p - cum[len(above) - 1] < 1e-5:
        pths.append(above[-1])
    through = int(np.sum(desc >= desc[bnd]))    # the boundary's ties too
    if through < v and cum[through - 1] - top_p < 1e-5:
        pths.append(desc[through])
    return kth, pths


_FILTERS = {"top_k": ((1, 5, 50, 300), (1.0,)),
            "top_p": ((0,), (0.95, 0.8, 0.5, 0.1)),
            "both": ((1, 5, 50, 300), (0.95, 0.8, 0.5, 0.1)),
            "neither": ((0,), (1.0,))}
_processed_jit = jax.jit(processed_logits)


@pytest.mark.parametrize("filters", sorted(_FILTERS))
@pytest.mark.parametrize("rows", [(6,), (2, 3)], ids=["B", "BxK"])
@pytest.mark.parametrize("vocab", [257, 19200, 50257])
def test_thresholds_by_selection_match_the_sorted_oracle(vocab, rows,
                                                         filters):
    """The kept set of every row is the float64 sort-and-cumsum
    oracle's, or one of its two neighbours where the oracle's boundary
    mass is within 1e-5 of ``top_p``: vocabularies that are and are
    not multiples of 128, decode- and verify-shaped rows, ties at both
    boundaries (logits on a grid of quarters), ``-inf`` columns, a
    greedy row, and each filter alone, both and neither."""
    ks, ps = _FILTERS[filters]
    rng = np.random.RandomState(vocab + len(rows) + len(filters))
    n = int(np.prod(rows))
    lg = (rng.randn(n, vocab) * rng.choice([0.5, 1.0, 3.0], size=(n, 1))
          ).astype(np.float32)
    lg[1] = np.round(lg[1] * 4) / 4             # ties everywhere
    lg[2] = np.round(lg[2])
    lg[3, rng.randint(0, vocab, size=vocab // 8)] = -np.inf
    lg[4] = np.where(np.arange(vocab) < 3, np.log([3.0, 1.0, 1.0])[
        np.minimum(np.arange(vocab), 2)], -np.inf)   # masses 0.6 0.2 0.2
    temp = rng.choice([0.5, 0.8, 1.0, 1.3], size=n).astype(np.float32)
    temp[4] = 1.0
    temp[5] = 0.0                               # the greedy lane's floor
    tk = rng.choice(ks, size=n).astype(np.int32)
    tp = rng.choice(ps, size=n).astype(np.float32)
    if filters != "top_k":
        tp[4] = 0.8 if filters != "neither" else 1.0    # 0.6 + 0.2: a tie
    got = np.asarray(_processed_jit(
        lg.reshape(rows + (vocab,)), temp.reshape(rows), tk.reshape(rows),
        tp.reshape(rows))).reshape(n, vocab)
    scaled = lg / np.maximum(temp, np.float32(1e-6))[:, None]
    for r in range(n):
        kth, pths = _oracle_thresholds(scaled[r], int(tk[r]), float(tp[r]))
        sets = [np.where(scaled[r] >= max(kth, pth), scaled[r], -np.inf)
                for pth in pths]
        assert any(np.array_equal(got[r], want) for want in sets), (
            r, int(tk[r]), float(tp[r]), int(np.sum(got[r] > -np.inf)),
            [int(np.sum(w > -np.inf)) for w in sets])
        assert np.sum(got[r] > -np.inf) >= 1


def test_sweep_times_the_sampler_alone_and_gives_no_time_without_a_chip(
        capsys):
    """``tools/perf_sweep.py::sweep_sampler`` launches the sampler alone
    at the shapes it is given and prints the share of a sampled row the
    mask keeps; its time is the program's on the DEVICE's clock, so on
    the CPU a shape carries an error and no number."""
    import json
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools")
    sys.path.insert(0, tools)
    try:
        import perf_sweep
    finally:
        sys.path.remove(tools)
    assert perf_sweep.SAMPLER_SHAPES == (
        (8, 50257), (8, 5, 50257), (16, 5, 19200), (8, 128256))
    rows = perf_sweep.sweep_sampler(shapes=((8, 257), (2, 5, 257)), iters=1)
    assert [r["shape"] for r in rows] == [[8, 257], [2, 5, 257]]
    for r in rows:
        assert 60 < r["kept_pct"] < 90      # a wide nucleus, as the cells'
        assert "ms" not in r and "error" in r
    printed = [json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith("{")]
    assert printed == rows


# -- the server: greedy default bit-parity + fast paths --------------------

def test_server_default_greedy_bit_identical(tiny):
    """``sampling=None``, explicit ``SamplingParams()``, and the
    pre-sampling submit signature are byte-identical — the default
    path is untouched."""
    cfg, params = tiny
    prompts, _ = _prompts_and_params()
    a = _server(cfg, params).generate(prompts, 16)
    b = _server(cfg, params).generate(prompts, 16,
                                      sampling=SamplingParams())
    assert a == b


def test_stochastic_keeps_fast_paths(tiny):
    """The headline: stochastic requests run with speculation AND the
    pipelined loop ON — drafts fire, verify launches, and the
    sampling stats account the traffic."""
    cfg, params = tiny
    prompts, samp = _prompts_and_params()
    server = _server(cfg, params)
    assert server.pipelining and server.speculating
    outs = server.generate(prompts, 16, sampling=samp)
    assert all(len(o) == 16 for o in outs)
    st = server.stats()
    assert st["speculation"]["enabled"]
    assert st["pipeline"]["enabled"]
    assert st["pipeline"]["launches"] > 0
    assert st["speculation"]["verify_steps"] > 0
    assert st["sampling"]["requests"].get("top_p") == len(prompts)
    rej = st["sampling"]["rejection"]
    assert rej["drafted_tokens"] > 0
    assert rej["resamples"] + rej["accepted_tokens"] > 0
    # the same seeds replayed on the synchronous loop without
    # speculation (the logits programs, sampled on the host's side of
    # the same sampler): the same streams
    plain = _server(cfg, params, enable_pipeline=False,
                    enable_speculation=False)
    assert plain.generate(prompts, 16, sampling=samp) == outs


def test_pinned_sampling_stats_block(tiny):
    """The stats()['sampling'] block's keys are pinned — dashboards
    key on them."""
    cfg, params = tiny
    server = _server(cfg, params)
    server.generate([[1, 2, 3]], 4)
    st = server.stats()["sampling"]
    assert set(st.keys()) == {"requests", "rejection"}
    assert set(st["rejection"].keys()) == {
        "drafted_tokens", "accepted_tokens", "acceptance_rate",
        "resamples"}
    assert st["requests"] == {"greedy": 1}


def test_submit_rejects_non_sampling_params(tiny):
    cfg, params = tiny
    server = _server(cfg, params)
    with pytest.raises(TypeError, match="SamplingParams"):
        server.submit([1, 2], 4, sampling={"temperature": 1.0})


def test_stochastic_eos_termination(tiny):
    """A sampled eos terminates exactly like greedy's."""
    cfg, params = tiny
    prompts, samp = _prompts_and_params()
    server = _server(cfg, params)
    reqs = server.generate(prompts, 24, eos_id=3, sampling=samp,
                           return_requests=True)
    for r in reqs:
        assert r.finish_reason in ("eos", "length")
        if r.finish_reason == "eos":
            assert r.generated[-1] == 3
            assert 3 not in r.generated[:-1]


# -- determinism across every serving path (the coupling invariance) -------

@pytest.mark.slow
def test_stochastic_replay_and_path_invariance(tiny):
    """One stochastic workload, byte-identical across: same-seed
    replay, speculation on/off, pipeline on/off, a starved pool
    (forced preemption + prefix-cache eviction), and prompts fed
    in several chunks where the reference (a chunk as wide as the
    context) feeds each in one — the Gumbel-max coupling makes every fast path a
    pure reordering for stochastic traffic too."""
    cfg, params = tiny
    prompts, samp = _prompts_and_params(5)
    ref = _server(cfg, params).generate(prompts, 24, sampling=samp)
    variants = {
        "replay": {},
        "spec_off": {"enable_speculation": False},
        "pipeline_off": {"enable_pipeline": False},
        "both_off": {"enable_pipeline": False,
                     "enable_speculation": False},
        "starved_pool": {"num_blocks": 30},
        "chunks_of_4": {"prefill_chunk": 4},
        "no_prefix_cache": {"enable_prefix_cache": False},
    }
    for name, kw in variants.items():
        server = _server(cfg, params, **kw)
        got = server.generate(prompts, 24, sampling=samp)
        assert got == ref, f"{name} diverged from the reference run"
        server.scheduler.audit()
    # the starved pool actually preempted (the variant is not vacuous)
    starved = _server(cfg, params, num_blocks=30)
    reqs = starved.generate(prompts, 24, sampling=samp,
                            return_requests=True)
    assert [list(r.generated) for r in reqs] == ref


@pytest.mark.slow
def test_mixed_batch_greedy_rows_bit_exact(tiny):
    """Greedy requests inside a mixed stochastic batch (which runs
    the stochastic program) emit the same bytes as an all-greedy
    run — the in-trace greedy lane is argmax, not temperature~0."""
    cfg, params = tiny
    prompts, _ = _prompts_and_params(4)
    all_greedy = _server(cfg, params).generate(prompts, 20)
    mixed = [None, SamplingParams(temperature=0.9, seed=5), None,
             SamplingParams(temperature=0.7, top_k=8, seed=6)]
    got = _server(cfg, params).generate(prompts, 20, sampling=mixed)
    assert got[0] == all_greedy[0]
    assert got[2] == all_greedy[2]
    assert got[1] != all_greedy[1] or got[3] != all_greedy[3]


# -- vocab-parallel stochastic parity (tp in {2, 4}) -----------------------

@pytest.mark.parametrize("tp", [2, 4])
def test_vocab_parallel_stochastic_parity(tp):
    """The sharded sampler's token streams are bit-identical to the
    unsharded one — greedy and stochastic rows, divisible and padded
    vocabs, decode-shaped (B, V) and verify-shaped (B, K, V)
    batches."""
    from jax.sharding import Mesh

    from apex_tpu.ops.vocab_parallel import vocab_parallel_sample_tokens

    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("model",))
    rng = np.random.RandomState(7)
    for shape, v in (((6,), 64), ((3, 4), VOCAB)):
        logits = (rng.randn(*shape, v) * 2.0).astype(np.float32)
        temp = rng.uniform(0.3, 1.5, size=shape).astype(np.float32)
        temp.flat[0] = 0.0                      # one greedy row
        tk = rng.choice([0, 3, 8], size=shape).astype(np.int32)
        tp_ = rng.choice([1.0, 0.9, 0.7], size=shape).astype(
            np.float32)
        seed = rng.randint(0, 1000, size=shape).astype(np.int32)
        pos = rng.randint(0, 100, size=shape).astype(np.int32)
        ref_ids, ref_fin = sample_tokens_host(logits, temp, tk, tp_,
                                              seed, pos)
        got_ids, got_fin = vocab_parallel_sample_tokens(
            jnp.asarray(logits), temp, tk, tp_, seed, pos, mesh)
        assert np.array_equal(np.asarray(ref_ids),
                              np.asarray(got_ids)), (shape, v)
        assert np.array_equal(np.asarray(ref_fin),
                              np.asarray(got_fin))


@pytest.mark.slow
def test_tp_server_stochastic_parity(tiny):
    """End-to-end: a tensor-parallel server generates the same
    stochastic streams as the unsharded engine — the full vertical
    (stochastic twins + no-gather sharded sampler + retire
    transfer)."""
    from jax.sharding import Mesh

    cfg, params = tiny
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    prompts, samp = _prompts_and_params(4)
    ref = _server(cfg, params).generate(prompts, 20, sampling=samp)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    got = _server(cfg, params, mesh=mesh).generate(prompts, 20,
                                                   sampling=samp)
    assert got == ref
