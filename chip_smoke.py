"""The quickest proof that the system still starts on the chip.

One process drives the two main paths through the public API, at the
published width of ``models.gpt_small()`` (12 layers, hidden 768, 12
heads of 64, vocabulary 50257, sequence 1024) with random weights made
from a seed:

- *kernels*: ``tools/kernel_parity.py``'s checks, compiled, plus the
  two ``ops.cached_attention`` rows at the server's decode shape;
- *trainer*: ``amp.initialize(GPTLMHeadModel + causal flash,
  FusedAdam, "O2")``, batch 8 x 1024, one fixed batch repeated;
- *server*: ``InferenceServer(cfg, params, max_batch_size=8)`` with the
  constructor's defaults, ten requests in two waves, every emitted
  token checked against a full-recompute forward;
- *trainer4* (four or more devices): the trainer at batch 16 over a
  ``("data",)`` mesh of four, against a one-device oracle.

Each phase prints its compile seconds and its run seconds.  The last
two lines of standard output are JSON objects: first the report (the
versions, the compile-cache directory, each phase's result), then the
verdict, ``{"ok": ..., "device": {"platform", "kind", "count"}}`` with
exactly those keys, which is what the driver's chip check reads.  The
exit code is 0 only if every phase passed.  Without a TPU the script
exits non-zero before any phase and prints no result.  It sets no
platform itself.

    python chip_smoke.py                  # every phase the devices allow
    python chip_smoke.py --phases server  # one phase, while debugging

The phases are functions that take their sizes:
``tests/L0/test_chip_smoke.py`` runs the same control flow tiny on the
CPU mesh before chip time is spent.
"""

import argparse
import contextlib
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp, models, optimizers, parallel
from apex_tpu.ops import native
from apex_tpu.ops.flash_attention import make_flash_attention
from apex_tpu.ops.pallas_utils import require_tpu
from apex_tpu.serving import InferenceServer
from apex_tpu.utils.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.abspath(__file__))

TRAIN_KERNELS = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel",
                 "_ln_fwd_kernel", "_ln_bwd_kernel", "_adam_kernel")
# ln(50257) = 10.82: the loss of a uniform guess over the vocabulary
STEP0_LOSS = (10.5, 11.2)
# prompts of the server phase: one block-aligned (resubmitted whole in
# the second wave, which forces a copy-on-write block copy), three
# longer than the 256-token prefill chunk
PROMPT_LENS = (16, 40, 64, 100, 180, 300, 450, 700)


# -- compile/run accounting ------------------------------------------------

class _CompileClock:
    """Seconds in XLA's backend compile (a persistent-cache hit counts
    its retrieval instead) and the cache's hits and misses, from JAX's
    own monitoring events.  Tracing and lowering are host work and
    stay in a phase's run seconds."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextlib.contextmanager
def _phase(name, clock, results):
    """Run one phase: time it, split compile from run, record its
    result dict (the body fills it) or its failure."""
    out = {"ok": False}
    results[name] = out
    t0, c0 = time.perf_counter(), clock.seconds
    h0, m0 = clock.hits, clock.misses
    try:
        yield out
        out["ok"] = True
    except Exception as e:      # a failed phase must not hide the others
        import traceback
        traceback.print_exc()
        out["error"] = f"{type(e).__name__}: {e}"[:2000]
    wall = time.perf_counter() - t0
    out["compile_s"] = round(clock.seconds - c0, 2)
    out["run_s"] = round(wall - out["compile_s"], 2)
    out["cache_hits"] = clock.hits - h0
    out["cache_misses"] = clock.misses - m0
    print(f"[{name}] ok={out['ok']} compile_s={out['compile_s']} "
          f"run_s={out['run_s']} cache_hits={out['cache_hits']} "
          f"cache_misses={out['cache_misses']}", flush=True)


def mosaic_calls(hlo_text, names):
    """How many Mosaic custom calls of each kernel name the compiled
    HLO holds (the kernels pass their name to ``pallas_call``, which
    lands in the call's ``op_name``)."""
    return {n: len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="[^"]*/%s/pallas_call' % re.escape(n), hlo_text))
        for n in names}


# -- kernels -----------------------------------------------------------------

def phase_kernels(out, *, decode_shape, **run_checks_kw):
    """``tools/kernel_parity.py``'s rows, in-process; on a TPU they are
    compiled, never interpreted (``interpret=not on_tpu()``)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import kernel_parity

    rows = kernel_parity.run_checks(decode_shape=decode_shape,
                                    **run_checks_kw)
    out["rows"] = len(rows)
    out["failed"] = [r for r in rows if not r["pass"]]
    out["max_rel_err"] = max(r["rel_err"] for r in rows)
    assert not out["failed"], out["failed"]


# -- trainer -----------------------------------------------------------------

def build_trainer(cfg, mesh=None, lr=3e-4):
    """The amp-O2 GPT step of ``examples/gpt/main_amp.py``: on a mesh,
    one fully-manual ``shard_map`` region with the DDP all-reduce."""
    model, optimizer = amp.initialize(
        models.GPTLMHeadModel(
            cfg, attention_fn=make_flash_attention(causal=True)),
        optimizers.FusedAdam(lr=lr), opt_level="O2", verbosity=0)
    ddp = parallel.DistributedDataParallel(process_group="data")

    def loss_of(params, ids):
        return models.lm_loss(model.apply({"params": params}, ids), ids)

    def step(params, opt_state, ids):
        def loss_fn(p):
            loss = loss_of(p, ids)
            with amp.scale_loss(loss, opt_state) as scaled:
                return scaled, loss
        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        if mesh is not None:
            grads = ddp.reduce_gradients(grads)
            loss = jax.lax.pmean(loss, "data")
        params, opt_state = optimizer.step(params, grads, opt_state)
        return params, opt_state, loss

    if mesh is not None:
        step = jax.shard_map(step, mesh=mesh,
                             in_specs=(P(), P(), P("data")),
                             out_specs=(P(), P(), P()), check_vma=False)
    return model, optimizer, jax.jit(step, donate_argnums=(0, 1)), loss_of


def phase_trainer(out, cfg, *, batch, seq, steps=6, kernels=TRAIN_KERNELS,
                  step0_loss=STEP0_LOSS, sync_tol=0.10, mesh=None,
                  seed=0):
    """Compile, check the HLO, take ``1 + 4 * steps`` steps on one
    fixed batch.  Returns what the four-chip phase compares against."""
    model, optimizer, train_step, loss_of = build_trainer(cfg, mesh)
    ids_host = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    if mesh is None:
        repl = split = jax.devices()[0]
    else:
        repl = NamedSharding(mesh, P())
        split = NamedSharding(mesh, P("data"))
    ids = jax.device_put(ids_host, split)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, seq), jnp.int32))["params"]
    # the one-device oracle's copy: the step donates its own
    params0 = (jax.tree.map(np.asarray, params) if mesh is not None
               else None)
    params = jax.device_put(params, repl)
    opt_state = jax.device_put(optimizer.init(params), repl)

    compiled = train_step.lower(params, opt_state, ids).compile()
    out["mosaic_calls"] = mosaic_calls(compiled.as_text(), kernels)
    missing = [k for k, n in out["mosaic_calls"].items() if n == 0]
    assert not missing, f"no Mosaic call in the compiled step: {missing}"

    losses = []
    params, opt_state, loss = compiled(params, opt_state, ids)
    losses.append(float(loss))

    def window(close):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = compiled(params, opt_state, ids)
        close(loss)
        dt = time.perf_counter() - t0
        losses.append(float(loss))
        return dt

    # the same window closed two ways, twice over: if block_until_ready
    # returned before the device finished, its windows would be shorter
    t_block, t_fetch = [], []
    for _ in range(2):
        t_block.append(window(jax.block_until_ready))
        t_fetch.append(window(float))
    # with parameters and optimizer state still live; not every
    # backend reports memory (the CPU does not)
    placed = jax.tree.leaves(params)[0].sharding.device_set
    used = [d.memory_stats() for d in sorted(placed, key=lambda d: d.id)]
    if all(used):
        out["bytes_in_use"] = [u["bytes_in_use"] for u in used]
    out["losses"] = [round(x, 4) for x in losses]
    out["loss_scale"] = float(optimizer.loss_scale(opt_state))
    out["window_steps"] = steps
    out["window_block_until_ready_s"] = round(min(t_block), 4)
    out["window_float_loss_s"] = round(min(t_fetch), 4)
    out["sync_ratio"] = round(min(t_block) / min(t_fetch), 3)
    print(f"  losses {out['losses']} loss_scale {out['loss_scale']} "
          f"block_until_ready {out['window_block_until_ready_s']}s "
          f"float(loss) {out['window_float_loss_s']}s per {steps} steps",
          flush=True)

    assert step0_loss[0] <= losses[0] <= step0_loss[1], losses[0]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    assert out["loss_scale"] >= 1.0, out["loss_scale"]
    if sync_tol is not None:
        assert abs(out["sync_ratio"] - 1.0) <= sync_tol, out["sync_ratio"]
    return ids, ids_host, params0, loss_of, losses[0]


def phase_trainer4(out, cfg, *, batch, seq, steps=6, oracle_tol=2e-2,
                   **trainer_kw):
    """The trainer over a ("data",) mesh of four, kernels still in the
    compiled program, against the same batch on one device."""
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("data",))
    ids, ids_host, params0, loss_of, loss0 = phase_trainer(
        out, cfg, batch=batch, seq=seq, steps=steps, mesh=mesh,
        **trainer_kw)

    shard_devices = {s.device for s in ids.addressable_shards}
    out["batch_shards"] = len(ids.addressable_shards)
    assert shard_devices == set(devices), shard_devices
    assert all(s.data.shape[0] == batch // 4
               for s in ids.addressable_shards)
    if "bytes_in_use" in out:
        assert len(out["bytes_in_use"]) == 4
        assert all(b > 0 for b in out["bytes_in_use"]), out["bytes_in_use"]

    # the one-device oracle: same 16 sequences, same parameters
    one = jax.devices()[0]
    oracle = float(jax.jit(loss_of)(jax.device_put(params0, one),
                                    jax.device_put(ids_host, one)))
    out["oracle_loss"] = round(oracle, 5)
    out["oracle_diff"] = round(abs(oracle - loss0), 6)
    out["oracle_tol"] = oracle_tol
    print(f"  step-0 loss {loss0:.5f} on four devices, {oracle:.5f} on "
          f"one (tolerance {oracle_tol})", flush=True)
    assert abs(oracle - loss0) <= oracle_tol, (oracle, loss0)

    # grouped collectives: one formulation, inside a default shard_map
    from apex_tpu.parallel.collectives import psum_g
    got = jax.jit(jax.shard_map(
        lambda x: psum_g(x, "data", [[0, 1], [2, 3]]), mesh=mesh,
        in_specs=P("data"), out_specs=P("data")))(
            jnp.arange(1.0, 5.0))
    out["psum_g_groups"] = np.asarray(got).tolist()
    assert out["psum_g_groups"] == [3.0, 3.0, 7.0, 7.0], got

    # what the shard_map is for: the same kernel in a GSPMD-sharded jit
    # is refused at lowering (recorded, not asserted)
    from apex_tpu.normalization import fused_layer_norm_affine
    h = cfg.hidden_size
    x = jax.device_put(jnp.ones((batch, h), jnp.float32),
                       NamedSharding(mesh, P("data")))
    try:
        jax.jit(lambda x: fused_layer_norm_affine(
            x, jnp.ones((h,)), jnp.zeros((h,)), (h,), 1e-5, True)
        ).lower(x)
        out["gspmd_refuses_mosaic"] = False
    except NotImplementedError as e:
        out["gspmd_refuses_mosaic"] = "automatically partitioned" in str(e)


# -- server ------------------------------------------------------------------

def _recompute_gaps(cfg, params, requests, max_new):
    """For every emitted token, how far its logit lies below the
    maximum in a full-recompute forward over prompt plus output (0 =
    the argmax).  Random weights give near-ties in bf16, so this and
    not token equality is the test."""
    n = len(requests)
    full = [list(r.prompt) + list(r.generated) for r in requests]
    width = -(-max(len(f) for f in full) // 128) * 128
    width = min(width, cfg.max_position_embeddings)
    ids = np.zeros((n, width), np.int32)
    for i, f in enumerate(full):
        ids[i, :len(f)] = f
    # logits at position p predict token p + 1
    starts = np.asarray([len(r.prompt) - 1 for r in requests], np.int32)
    toks = np.zeros((n, max_new), np.int32)
    valid = np.zeros((n, max_new), bool)
    for i, r in enumerate(requests):
        toks[i, :len(r.generated)] = r.generated
        valid[i, :len(r.generated)] = True
    model = models.GPTLMHeadModel(cfg)

    @jax.jit
    def gaps(params, ids, starts, toks):
        logits = model.apply({"params": params}, ids)
        win = jax.vmap(lambda l, s: jax.lax.dynamic_slice_in_dim(
            l, s, max_new, 0))(logits, starts)          # (n, new, V)
        picked = jnp.take_along_axis(win, toks[..., None], -1)[..., 0]
        return jnp.max(win, -1) - picked

    g = np.asarray(gaps(params, ids, starts, toks))
    return np.where(valid, g, 0.0), valid


def phase_server(out, cfg, params, *, prompt_lens=PROMPT_LENS,
                 max_new=32, max_batch_size=8, shared_prefix=96,
                 gap_tol=0.1, expect_mosaic=True, seed=0):
    """Three waves through ``InferenceServer`` as shipped.  The second
    resubmits one block-aligned prompt whole (a copy-on-write block
    copy) and one prompt that shares ``shared_prefix`` tokens with a
    first-wave prompt; the third is one two-token request.  Together
    the chunk, decode, verify and copy programs each launch with their
    on-chip donation."""
    rng = np.random.RandomState(seed)
    wave1 = [rng.randint(0, cfg.vocab_size, n).tolist()
             for n in prompt_lens]
    server = InferenceServer(cfg, params, max_batch_size=max_batch_size)
    bs = server.engine.block_size
    aligned = next(p for p in wave1 if len(p) % bs == 0
                   and len(p) >= 2 * bs)
    donor = next(p for p in wave1 if len(p) > shared_prefix + bs)
    wave2 = [list(aligned),
             donor[:shared_prefix]
             + rng.randint(0, cfg.vocab_size, 2 * bs).tolist()]
    reqs = server.generate(wave1, max_new, return_requests=True)
    reqs += server.generate(wave2, max_new, return_requests=True)
    # a draft never covers a request's last token, so a two-token
    # request takes its second from the plain decode program whatever
    # the speculation did above
    reqs += server.generate([wave1[0]], 2, return_requests=True)
    st = server.stats()

    out["requests"] = len(reqs)
    out["finish_reasons"] = sorted({r.finish_reason for r in reqs})
    assert all(r.finish_reason in ("length", "eos") for r in reqs), \
        [(r.uid, r.finish_reason) for r in reqs]
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)
    out["requests_failed"] = st["requests_failed"]
    assert st["requests_failed_total"] == 0, st["requests_failed"]
    assert st["oom_events"] == 0 and st["preemptions"] == 0, st

    launched = {}
    for key, rec in st["programs"]["by_program"].items():
        fam = key.split("[")[0]
        launched[fam] = launched.get(fam, 0) + rec["calls"]
    out["program_calls"] = launched
    out["prefix_cow_blocks"] = st["prefix_cow_blocks"]
    out["prefix_hit_tokens"] = st["prefix_hit_tokens"]
    out["speculation"] = {k: st["speculation"][k] for k in (
        "drafted_tokens", "accepted_tokens", "verify_steps",
        "decode_steps")}
    print(f"  programs {launched} cow_blocks {out['prefix_cow_blocks']} "
          f"prefix_hit_tokens {out['prefix_hit_tokens']} "
          f"speculation {out['speculation']}", flush=True)
    for fam in ("chunk_prefill_sampled", "decode_sampled",
                "verify_sampled", "copy_blocks"):
        assert launched.get(fam, 0) >= 1, (fam, launched)
    assert out["prefix_hit_tokens"] >= shared_prefix // bs * bs

    if expect_mosaic:
        calls = mosaic_calls(server.engine.decode_hlo(),
                             ("_decode_kernel",))
        out["mosaic_calls"] = calls
        assert calls["_decode_kernel"] >= 1, calls

    gaps, valid = _recompute_gaps(cfg, params, reqs, max_new)
    out["tokens_checked"] = int(valid.sum())
    out["tokens_argmax"] = int((valid & (gaps == 0.0)).sum())
    out["logit_gap_max"] = round(float(gaps.max()), 5)
    out["logit_gap_tol"] = gap_tol
    print(f"  {out['tokens_argmax']} of {out['tokens_checked']} emitted "
          f"tokens are the recompute's argmax; largest logit gap "
          f"{out['logit_gap_max']} (tolerance {gap_tol})", flush=True)
    assert gaps.max() <= gap_tol, out["logit_gap_max"]

    server.close()
    assert server.closed


# -- entry -------------------------------------------------------------------

def _versions():
    import importlib.metadata as md
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = None
    return out


def result_lines(ok, dev, n_dev, report):
    """The last two lines of standard output: the report, then the
    verdict with exactly the keys the driver's chip check expects."""
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    return (json.dumps({"ok": ok, "device": device, **report}),
            json.dumps({"ok": ok, "device": device}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--phases", default="kernels,trainer,server,trainer4",
                    help="comma-separated subset, for debugging")
    args = ap.parse_args()
    phases = args.phases.split(",")

    dev = require_tpu()          # exits non-zero, naming what it found
    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    n_dev = len(jax.devices())
    cfg = models.gpt_small()
    seq = cfg.max_position_embeddings
    print(f"chip_smoke: {n_dev} x {dev.device_kind}, {cfg}, "
          f"compile cache {cache_dir}, native.available="
          f"{native.available} native.jpeg_available="
          f"{native.jpeg_available}", flush=True)

    results = {}
    if "kernels" in phases:
        with _phase("kernels", clock, results) as out:
            phase_kernels(out, decode_shape=(
                8, seq, cfg.num_attention_heads,
                cfg.hidden_size // cfg.num_attention_heads))
    if "trainer" in phases:
        with _phase("trainer", clock, results) as out:
            phase_trainer(out, cfg, batch=8, seq=seq)
    if "server" in phases:
        params = models.GPTLMHeadModel(cfg).init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
        with _phase("server", clock, results) as out:
            phase_server(out, cfg, params)
        del params
    if "trainer4" in phases and n_dev >= 4:
        with _phase("trainer4", clock, results) as out:
            phase_trainer4(out, cfg, batch=16, seq=seq)

    ok = bool(results) and all(r["ok"] for r in results.values())
    for line in result_lines(ok, dev, n_dev, {
            "versions": _versions(),
            "compile_cache_dir": cache_dir,
            "native": {"available": native.available,
                       "jpeg_available": native.jpeg_available},
            "layers": cfg.num_hidden_layers,
            "four_chip_phase": "trainer4" in results,
            "phases": results}):
        print(line, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
