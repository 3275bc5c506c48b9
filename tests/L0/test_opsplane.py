"""Ops plane, hang watchdog, per-program accounting.

The live-observability acceptance oracles (``docs/observability.md``,
"Ops plane & watchdog"):

- **headline**: with the ops server enabled, ``/healthz``,
  ``/metrics``, ``/statusz``, ``/debug/flight``, and
  ``/debug/requests/<uid>`` all serve live data over real HTTP from a
  running server — ``/metrics`` under the Prometheus
  ``text/plain; version=0.0.4`` content type and passing the same
  line-grammar conformance check as the in-process exposition test —
  and the loopback-authenticated POST triggers drive ``drain()`` /
  ``dump_postmortem()``;
- a forced hang trips the watchdog EXACTLY once (no re-fire while the
  stall persists, no false positive on warmup compiles — the slowest
  healthy steps there are), flips ``/healthz`` to 503 ``"stalled"``,
  recovers to 200 when the loop resumes, and leaves a postmortem
  bundle with every thread's stack attached that
  ``tools/postmortem.py --assert-complete`` gates;
- the disabled watchdog path adds ZERO allocations per step
  (tracemalloc-bounded, the ``NULL_FLIGHT_RECORDER`` contract), and
  detection logic is provable on an injected clock without threads
  or sleeps;
- ``stats()`` carries pinned ``programs`` / ``watchdog`` / ``ops``
  blocks (the PR-7 ``slo``/``memory`` pin pattern), and the program
  table's call/compile accounting reconciles with the engine's
  compile audit;
- none of it feeds back: a seeded chaos soak with the watchdog armed
  records zero stalls and reproduces the unarmed report.
"""

import json
import os
import re
import sys
import threading
import time
import tracemalloc
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import models
from apex_tpu.observability import (
    NULL_WATCHDOG,
    FlightRecorder,
    HangWatchdog,
    MetricsRegistry,
    OPS_PORT_ENV,
    ProgramAccounting,
)
from apex_tpu.serving import InferenceServer

pytestmark = pytest.mark.serving

VOCAB = 61

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))


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
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", 8)
    return InferenceServer(cfg, params, **kw)


def _get(base, path, timeout=10.0):
    """(status, headers, body) without raising on HTTP errors — a 503
    is an ANSWER from /healthz, not a failure."""
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _post(base, path, timeout=30.0):
    req = urllib.request.Request(base + path, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


# -- headline: every endpoint serves live data over real HTTP --------------


def test_ops_endpoints_serve_live_data(tiny, tmp_path):
    cfg, params = tiny
    pm = str(tmp_path / "pm")
    server = _server(cfg, params, ops_port=0, postmortem_dir=pm,
                     flight_recorder=FlightRecorder())
    try:
        assert server.ops is not None and server.ops.port > 0
        base = f"http://127.0.0.1:{server.ops.port}"
        server.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=4)

        code, _, body = _get(base, "/healthz")
        health = json.loads(body)
        assert code == 200 and health["status"] == "ok"
        assert health["breaker"] == "closed"
        assert health["watchdog_stalls"] == 0
        # the router-scrape trio (docs/serving.md, "Multi-replica
        # routing"): one cheap endpoint carries the placement signal,
        # the lifecycle flag, and the occupancy — machine-readable,
        # no /statusz parse
        assert isinstance(health["pressure"], float)
        assert health["draining"] is False
        assert health["live_requests"] == 0      # idle post-generate
        # the streaming tier's probe pair (docs/serving.md,
        # "Streaming & cancellation"): open-stream gauge + lifetime
        # backpressure drop counter ride the cheap endpoint too
        assert health["active_streams"] == 0
        assert health["stream_backpressure_drops"] == 0

        code, headers, body = _get(base, "/metrics")
        assert code == 200
        assert headers["Content-Type"] == \
            "text/plain; version=0.0.4; charset=utf-8"
        text = body.decode()
        assert "serving_step_s_count" in text
        # the decode phase's program, whichever launched: so short a
        # prompt is extended by the n-gram drafter itself, and every
        # step of it may be a verify launch
        assert re.search(r'serving_program_calls\{program="'
                         r'(decode_sampled|verify_sampled\[5\])"\}',
                         text)
        # live scrape equals the in-process exposition modulo the ops
        # request counters the scrape itself bumps
        assert text.startswith("# HELP")

        code, _, body = _get(base, "/statusz")
        stats = json.loads(body)
        assert code == 200
        assert stats["requests_finished"] == 2
        assert {"programs", "watchdog", "ops", "slo",
                "memory"} <= stats.keys()
        assert stats["ops"]["enabled"] is True
        assert stats["ops"]["port"] == server.ops.port
        assert stats["ops"]["requests"] >= 2      # counted so far

        code, _, body = _get(base, "/debug/flight?n=3")
        records = [json.loads(ln) for ln in body.splitlines()]
        assert code == 200 and 1 <= len(records) <= 3
        assert all("iter" in r and "memory" in r for r in records)

        uid = server.scheduler.finished[0].uid
        code, _, body = _get(base, f"/debug/requests/{uid}")
        req = json.loads(body)
        assert code == 200 and req["state"] == "finished"
        assert req["timeline"]["uid"] == uid
        assert req["timeline"]["finish_reason"] == "length"
        code, _, _ = _get(base, "/debug/requests/999999")
        assert code == 404
        code, _, _ = _get(base, "/nope")
        assert code == 404

        # POST triggers: postmortem writes a gateable bundle, drain
        # flips healthz to 503/draining
        code, body = _post(base, "/postmortem")
        pm_resp = json.loads(body)
        assert code == 200
        assert pm_resp["manifest"]["reason"] == "ops_request"
        assert os.path.isfile(os.path.join(pm_resp["path"],
                                           "manifest.json"))
        code, body = _post(base, "/drain")
        assert code == 200
        assert json.loads(body)["status"] == "drained"
        code, _, body = _get(base, "/healthz")
        assert code == 503
        health = json.loads(body)
        assert health["status"] == "draining"
        assert health["draining"] is True
    finally:
        server.close()


def test_live_metrics_scrape_is_prometheus_conformant(tiny):
    """The satellite contract: the conformance judgment applied to the
    in-process string (``test_observability.py``) holds for the LIVE
    ``/metrics`` endpoint too — same grammar, plus the content type a
    scraper negotiates on."""
    import ops_probe

    cfg, params = tiny
    server = _server(cfg, params, ops_port=0)
    try:
        server.generate([[1, 2, 3]], max_new_tokens=4)
        base = f"http://127.0.0.1:{server.ops.port}"
        code, headers, body = _get(base, "/metrics")
        assert code == 200
        assert ops_probe.PROM_CONTENT_TYPE_RE.search(
            headers["Content-Type"])
        problems = ops_probe.check_prometheus_text(body.decode())
        assert not problems, problems
        # and the whole gate agrees over the wire
        assert ops_probe.main(["--port", str(server.ops.port),
                               "--assert-healthy"]) == 0
    finally:
        server.close()


def test_ops_off_by_default_and_env_twin(tiny, monkeypatch):
    cfg, params = tiny
    server = _server(cfg, params)
    assert server.ops is None and server._ops_lock is None
    st = server.stats()["ops"]
    assert st == {"enabled": False, "port": None, "requests": 0}
    server.close()
    monkeypatch.setenv(OPS_PORT_ENV, "0")
    server = _server(cfg, params)
    try:
        assert server.ops is not None and server.ops.port > 0
    finally:
        server.close()


# -- watchdog: deterministic detection on an injected clock ----------------


def test_watchdog_detects_in_step_hang_exactly_once():
    clk = FakeClock()
    fired = []
    wd = HangWatchdog(deadline_s=5.0, poll_interval_s=None,
                      clock=clk, on_stall=fired.append)
    # healthy cadence: start/finish under the deadline never fires
    for _ in range(3):
        wd.step_started()
        clk.advance(1.0)
        wd.step_finished(has_work=True)
        assert wd.check() is False
    # hang inside a step: one detection, latched while it persists
    wd.step_started()
    clk.advance(4.9)
    assert wd.check() is False               # under deadline
    clk.advance(0.2)
    assert wd.check() is True
    assert wd.stalled is True and wd.stalls == 1
    clk.advance(100.0)
    assert wd.check() is False               # latched: no re-fire
    assert wd.stalls == 1
    assert fired[0]["where"] == "in_step"
    assert fired[0]["deadline_s"] == 5.0
    # progress clears the latch and re-arms
    wd.step_finished(has_work=True)
    assert wd.stalled is False
    clk.advance(5.1)
    assert wd.check() is True                # loop died with work left
    assert wd.stalls == 2
    assert fired[1]["where"] == "between_steps"


def test_watchdog_idle_server_is_never_a_stall():
    clk = FakeClock()
    wd = HangWatchdog(deadline_s=1.0, poll_interval_s=None, clock=clk)
    wd.step_started()
    clk.advance(0.5)
    wd.step_finished(has_work=False)         # drained: nothing pending
    clk.advance(1e6)
    assert wd.check() is False and wd.stalls == 0
    # and a never-stepped server is idle too
    wd2 = HangWatchdog(deadline_s=1.0, poll_interval_s=None, clock=clk)
    clk.advance(1e6)
    assert wd2.check() is False


def test_watchdog_on_stall_exception_never_propagates(capsys):
    clk = FakeClock()

    def boom(info):
        raise RuntimeError("handler bug")

    wd = HangWatchdog(deadline_s=1.0, poll_interval_s=None,
                      clock=clk, on_stall=boom)
    wd.step_started()
    clk.advance(2.0)
    assert wd.check() is True                # detection still counted
    assert wd.stalls == 1
    assert "handler bug" in capsys.readouterr().err
    with pytest.raises(ValueError):
        HangWatchdog(deadline_s=0.0)


def test_disabled_watchdog_allocates_nothing_per_step():
    """The NULL pattern contract: the step loop guards heartbeats on
    ``watchdog.enabled``, so the disabled default costs zero
    allocations across 10k steps."""
    assert NULL_WATCHDOG.enabled is False
    assert NULL_WATCHDOG.stalled is False and NULL_WATCHDOG.stalls == 0
    assert NULL_WATCHDOG.check() is False
    NULL_WATCHDOG.start()
    NULL_WATCHDOG.stop()
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(10_000):
        if NULL_WATCHDOG.enabled:            # the step() guard
            NULL_WATCHDOG.step_started()
            NULL_WATCHDOG.step_finished(True)
    cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert cur - base < 2048, "disabled watchdog retained memory"
    assert peak - base < 8192, "disabled watchdog allocated per step"


# -- forced hang end-to-end ------------------------------------------------


def test_forced_hang_trips_once_flips_healthz_and_dumps_bundle(
        tiny, tmp_path):
    """The watchdog acceptance oracle: warmup (compiles) is
    false-positive-free, one wedged engine launch is detected exactly
    once, ``/healthz`` answers 503 DURING the hang (lock-free by
    design — the serve thread is holding the ops lock), recovery
    returns 200, and the bundle carries the wedged thread's stack and
    passes the CLI gate."""
    cfg, params = tiny
    pm = str(tmp_path / "pm")
    server = _server(
        cfg, params, ops_port=0, postmortem_dir=pm,
        watchdog=HangWatchdog(deadline_s=60.0, poll_interval_s=0.05))
    try:
        base = f"http://127.0.0.1:{server.ops.port}"
        server.generate([[1, 2, 3]], max_new_tokens=4)   # warmup
        assert server.stats()["watchdog"]["stalls"] == 0
        server.watchdog.deadline_s = 0.4

        class HangOnce:
            """Wedges the decode phase's first launch, whichever
            program it is: the n-gram drafter extends so short a
            prompt by itself, so every step of this request may be a
            ``verify_sampled`` launch and never a ``decode_sampled``
            one."""

            def __init__(self, inner):
                self.inner = inner
                self.hung = None

            def _hang_once(self, name, a, kw):
                if self.hung is None:
                    self.hung = name
                    time.sleep(1.6)
                return getattr(self.inner, name)(*a, **kw)

            def decode_sampled(self, *a, **kw):
                return self._hang_once("decode_sampled", a, kw)

            def verify_sampled(self, *a, **kw):
                return self._hang_once("verify_sampled", a, kw)

            def __getattr__(self, name):
                return getattr(self.inner, name)

        server.engine = HangOnce(server.engine)
        server.submit([1, 2, 3], max_new_tokens=6)
        t = threading.Thread(target=lambda: [
            server.step() for _ in iter(
                lambda: server.scheduler.has_work, False)])
        t.start()
        saw = None
        for _ in range(300):
            code, _, body = _get(base, "/healthz", timeout=2)
            if code == 503:
                saw = json.loads(body)["status"]
                break
            time.sleep(0.02)
        t.join(timeout=60)
        assert saw == "stalled"
        code, _, _ = _get(base, "/healthz")
        assert code == 200                       # recovered
        st = server.stats()["watchdog"]
        assert st["stalls"] == 1 and st["stalled"] is False

        bundles = [d for d in os.listdir(pm)
                   if d.startswith("watchdog_stall")]
        assert len(bundles) == 1
        bundle = os.path.join(pm, bundles[0])
        man = json.load(open(os.path.join(bundle, "manifest.json")))
        assert man["reason"] == "watchdog_stall"
        assert man["extra"]["stall"]["where"] == "in_step"
        threads = open(os.path.join(
            bundle, man["extra"]["thread_stacks"])).read()
        assert "_hang_once" in threads           # the wedged frame
        assert server.engine.hung in threads
        import postmortem as pm_cli
        assert pm_cli.main([bundle, "--assert-complete"]) == 0
        assert pm_cli.main([bundle, "--last-n-steps", "3"]) == 0
    finally:
        server.close()


@pytest.mark.chaos
def test_armed_watchdog_changes_nothing_on_healthy_soak(tiny):
    """Arming the watchdog (real clock, sane deadline) is observation
    only: the seeded soak reproduces the unarmed report exactly and
    records zero stalls — the false-positive trial run_soak asserts."""
    from apex_tpu.resilience import CircuitBreaker
    from apex_tpu.resilience.chaos import ChaosConfig, run_soak

    cfg, params = tiny

    def make(watchdog):
        def make_server(clock):
            return InferenceServer(
                cfg, params, max_batch_size=4, max_context=64,
                block_size=4, num_blocks=40, cache_dtype=jnp.float32,
                max_waiting=8, clock=clock, watchdog=watchdog,
                breaker=CircuitBreaker(failure_threshold=3,
                                       recovery_time=25.0,
                                       probe_successes=2, clock=clock))
        return make_server

    def make_replay(clock):
        return InferenceServer(
            cfg, params, max_batch_size=4, max_context=64,
            block_size=4, cache_dtype=jnp.float32, clock=clock)

    chaos_cfg = ChaosConfig(iters=120, vocab=VOCAB)
    armed = run_soak(
        make(HangWatchdog(deadline_s=60.0, poll_interval_s=0.1)),
        chaos_cfg, seed=3, make_replay=make_replay)
    unarmed = run_soak(make(None), chaos_cfg, seed=3,
                       make_replay=make_replay)
    assert armed["watchdog_stalls"] == 0 and armed["watchdog_armed"]
    assert not unarmed["watchdog_armed"]
    for key in ("submitted", "finished", "bit_exact_checked",
                "prefix_checked", "injected", "preemptions"):
        assert armed[key] == unarmed[key], key


# -- per-program accounting ------------------------------------------------


def test_program_accounting_unit_math():
    clk = FakeClock()
    reg = MetricsRegistry()
    acct = ProgramAccounting(registry=reg, clock=clk)
    t0 = acct.begin()
    clk.advance(2.0)
    acct.note("decode", t0, compiled=True)       # 2000ms compile call
    for _ in range(4):
        t0 = acct.begin()
        clk.advance(0.25)
        acct.note("decode", t0, compiled=False)  # 250ms steady calls
    t0 = acct.begin()
    clk.advance(1.0)
    acct.note("prefill[16]", t0, compiled=True)
    table = acct.table()
    assert set(table) == {"decode", "prefill[16]"}
    d = table["decode"]
    assert d["calls"] == 5 and d["compiles"] == 1
    assert d["wall_ms"] == pytest.approx(3000.0)
    assert d["compile_ms"] == pytest.approx(2000.0)
    assert d["steady_ms"] == pytest.approx(250.0)
    # a compile-only program has no steady figure yet
    assert table["prefill[16]"]["steady_ms"] == 0.0
    snap = reg.snapshot()
    assert snap['serving_program_calls{program="decode"}']["value"] \
        == 5
    assert snap['serving_program_compiles{program="decode"}'][
        "value"] == 1
    assert snap['serving_program_wall_s{program="decode"}'][
        "value"] == pytest.approx(3.0)


def test_program_table_reconciles_with_compile_audit(tiny):
    """The engine's compile-count audit and the program table count
    the same traces: summed per-program compiles equal the audited
    prefill+decode+verify totals, and steady-state calls outnumber
    compiles on a real run."""
    cfg, params = tiny
    server = _server(cfg, params)
    server.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
    st = server.stats()
    table = st["programs"]["by_program"]
    assert table, "accounting is on by default"
    pre, dec = server.engine.compile_counts()
    ver = server.engine.verify_compiles()
    assert sum(r["compiles"] for r in table.values()) == \
        pre + dec + ver + (1 if "copy_blocks" in table else 0)
    for key, row in table.items():
        assert row["calls"] >= row["compiles"] >= 0, key
        assert row["wall_ms"] >= row["compile_ms"] >= 0, key
    # the decode phase launched (as plain decode or, where the n-gram
    # drafter extends so short a prompt, as verify)
    assert [k for k in table if k.startswith(("decode", "verify"))]
    assert st["programs"]["total_wall_ms"] == pytest.approx(
        sum(r["wall_ms"] for r in table.values()), abs=0.01)


def test_engine_without_accounting_tallies_nothing(tiny):
    """``DecodeEngine(programs=None)`` (an engine built without a
    server) carries the null accounting: launches run, nothing is
    tallied and no clock is read for them."""
    from apex_tpu.observability import NULL_PROGRAM_ACCOUNTING
    from apex_tpu.serving import DecodeEngine

    cfg, params = tiny
    engine = DecodeEngine(cfg, params, max_batch_size=2, max_context=64,
                          block_size=8, cache_dtype=jnp.float32)
    assert engine.programs is NULL_PROGRAM_ACCOUNTING
    assert engine._mark(engine._chunk_jit) == (0.0, 0)
    engine.chunk_prefill([1, 2, 3], 0, engine.allocator.alloc(1),
                         pad_to=8)
    assert engine.compile_counts() == (1, 0)
    assert engine.programs.enabled is False
    assert engine.programs.table() == {}


# -- pinned stats blocks (the PR-7 slo/memory pin pattern) -----------------


def test_stats_programs_watchdog_ops_blocks_pinned(tiny):
    cfg, params = tiny
    server = _server(cfg, params)
    server.generate([[1, 2, 3]], max_new_tokens=4)
    st = server.stats()
    prog = st["programs"]
    assert set(prog) == {"enabled", "by_program", "attention",
                         "total_wall_ms", "total_compile_ms"}
    # the CPU backend gathers; the chip reads through the table
    assert prog["attention"] == {"decode": "gathered",
                                 "verify": "gathered",
                                 "chunk_prefill": "gathered"}
    assert prog["enabled"] is True
    for key, row in prog["by_program"].items():
        assert set(row) == {"calls", "compiles", "wall_ms",
                            "compile_ms", "steady_ms"}, key
    wd = st["watchdog"]
    assert set(wd) == {"enabled", "stalled", "stalls", "deadline_s"}
    assert wd == {"enabled": False, "stalled": False, "stalls": 0,
                  "deadline_s": None}
    ops = st["ops"]
    assert set(ops) == {"enabled", "port", "requests"}
    assert ops == {"enabled": False, "port": None, "requests": 0}
    # the streaming delivery tier (docs/serving.md, "Streaming &
    # cancellation"): broker counters + bounded per-stream rows on
    # by default; a disabled server keeps the two-key stub so
    # dashboards never KeyError on the block
    streams = st["streams"]
    assert set(streams) == {"enabled", "cancelled", "active",
                            "opened", "published_tokens",
                            "backpressure_drops", "finished",
                            "queue_tokens", "per_stream"}
    assert streams["enabled"] is True
    assert streams["cancelled"] == 0 and streams["active"] == 0
    off = _server(cfg, params, enable_streaming=False).stats()["streams"]
    assert off == {"enabled": False, "cancelled": 0}
