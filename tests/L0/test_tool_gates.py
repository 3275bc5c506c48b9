"""Exit-code contract of the gating CLI tools.

``tests/build_matrix/run.sh`` branches on the exit codes of
``tools/ops_probe.py --assert-healthy`` and ``tools/obs_dump.py
trace --require`` — a failure surfacing as an uncaught traceback
still exits nonzero by accident, but a failure that *passes* (or a
gate that dies on a malformed artifact before judging it) silently
un-gates an axis.  These tests pin the contract: every
assertion-style failure exits 1 with a ``FAIL:`` line and no
traceback; healthy inputs exit 0.
"""

import json
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

_CONFORMANT_METRICS = (
    "# HELP serving_tokens_total tokens produced\n"
    "# TYPE serving_tokens_total counter\n"
    "serving_tokens_total 5\n")

_STATUSZ = {"programs": {"by_program": {}, "enabled": True},
            "watchdog": {"stalls": 0}, "ops": {},
            "latency": {}, "memory": {}}


class _StubOps(BaseHTTPRequestHandler):
    """A canned ops plane: healthy by default, corruptible per-server
    via attributes on the HTTPServer instance."""

    def do_GET(self):
        srv = self.server
        if self.path == "/healthz":
            body = srv.healthz_body
            code = 200 if b'"ok"' in body else 503
            self._send(code, body, "application/json")
        elif self.path == "/metrics":
            self._send(200, srv.metrics_body, srv.metrics_ctype)
        elif self.path == "/statusz":
            self._send(200, srv.statusz_body, "application/json")
        elif self.path.startswith("/debug/journey/"):
            body = getattr(srv, "journey_body", None)
            if body is None:
                self._send(404, b'{"error": "unknown rid"}',
                           "application/json")
            else:
                self._send(200, body, "application/json")
        else:
            self._send(404, b"{}", "application/json")

    def _send(self, code, body, ctype):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):
        pass


@pytest.fixture()
def stub_ops():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StubOps)
    httpd.healthz_body = json.dumps(
        {"status": "ok", "iter": 3, "breaker": "closed",
         "pressure": 0.1}).encode()
    httpd.metrics_body = _CONFORMANT_METRICS.encode()
    httpd.metrics_ctype = "text/plain; version=0.0.4; charset=utf-8"
    httpd.statusz_body = json.dumps(_STATUSZ).encode()
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        yield httpd
    finally:
        httpd.shutdown()
        httpd.server_close()


def _probe(port, *flags):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "ops_probe.py"),
         "--port", str(port), "--timeout", "5", *flags],
        capture_output=True, text=True, timeout=60)


def _no_traceback(res):
    assert "Traceback" not in res.stderr, res.stderr
    assert "Traceback" not in res.stdout, res.stdout


def test_ops_probe_assert_healthy_passes_on_healthy_stub(stub_ops):
    res = _probe(stub_ops.server_address[1], "--assert-healthy")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_ops_probe_gates_on_unhealthy_status(stub_ops):
    stub_ops.healthz_body = json.dumps(
        {"status": "draining"}).encode()
    res = _probe(stub_ops.server_address[1], "--assert-healthy")
    assert res.returncode == 1
    assert "FAIL" in res.stderr
    _no_traceback(res)


def test_ops_probe_gates_on_nonconformant_metrics(stub_ops):
    stub_ops.metrics_body = b"!!! not prometheus text\n"
    res = _probe(stub_ops.server_address[1], "--assert-healthy")
    assert res.returncode == 1
    assert "not conformant" in res.stderr
    _no_traceback(res)


def test_ops_probe_gates_on_wrong_metrics_content_type(stub_ops):
    stub_ops.metrics_ctype = "text/html"
    res = _probe(stub_ops.server_address[1], "--assert-healthy")
    assert res.returncode == 1
    assert "content type" in res.stderr
    _no_traceback(res)


def test_ops_probe_gates_on_missing_statusz_blocks(stub_ops):
    stub_ops.statusz_body = json.dumps({"programs": {}}).encode()
    res = _probe(stub_ops.server_address[1], "--assert-healthy")
    assert res.returncode == 1
    assert "missing blocks" in res.stderr
    _no_traceback(res)


def test_ops_probe_clean_exit_on_connection_refused(stub_ops):
    stub_ops.shutdown()
    stub_ops.server_close()
    port = stub_ops.server_address[1]
    for flags in (("--assert-healthy",), ()):
        res = _probe(port, *flags)
        assert res.returncode == 1, res.stdout + res.stderr
        assert "FAIL" in res.stderr and "unreachable" in res.stderr
        _no_traceback(res)


def test_ops_probe_clean_exit_on_garbage_healthz_body(stub_ops):
    stub_ops.healthz_body = b'"status": "ok"  % garbage'
    # default mode (no flags) parses the body too — both must gate
    for flags in (("--assert-healthy",), ()):
        res = _probe(stub_ops.server_address[1], *flags)
        assert res.returncode == 1
        assert "FAIL" in res.stderr
        _no_traceback(res)


# -- ops_probe --elastic ---------------------------------------------------


_ELASTIC_BLOCK = {
    "enabled": True, "replicas": 2, "retired": 1,
    "min_replicas": 1, "max_replicas": 3,
    "pressure_avg": 0.91, "debt_delta": 12, "score": 1.03,
    "band": {"up": 0.85, "down": 0.25},
    "scale_ups": 1, "scale_downs": 1, "retiring": None,
    "cooldown": {"up_ready": False, "down_ready": True},
    "last_action": "scale_up",
    "weights_versions": {"initial": 2},
    "last_rollout": None,
    "decisions": [
        {"kind": "elastic", "action": "scale_up", "iter": 40,
         "t": 40.0, "pressure_avg": 0.91, "debt_delta": 12,
         "score": 1.03, "replicas": 2, "replica": "replica1",
         "warmed_blocks": 8},
    ],
}


def test_ops_probe_elastic_renders_decision_table(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["elastic"] = _ELASTIC_BLOCK
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--elastic")
    assert res.returncode == 0, res.stdout + res.stderr
    # the decision table carries the action AND its trigger signals
    assert "scale_up" in res.stdout
    assert "replica=replica1" in res.stdout
    assert "warmed_blocks=8" in res.stdout
    assert "1.03" in res.stdout          # the score it fired on


def test_ops_probe_elastic_gates_on_missing_block(stub_ops):
    res = _probe(stub_ops.server_address[1], "--elastic")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "elastic" in res.stderr
    _no_traceback(res)


def test_ops_probe_elastic_gates_on_disabled_autoscaler(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["elastic"] = dict(_ELASTIC_BLOCK, enabled=False)
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--elastic")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "disabled" in res.stderr
    _no_traceback(res)


def test_elastic_flags_advertised_by_gating_tools():
    """The build-matrix ``elastic`` axis invokes every tool below
    with ``--elastic`` — a dropped flag would fail the axis with an
    argparse error instead of a judged result."""
    for tool in ("chaos_soak.py", "ops_probe.py"):
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / tool), "--help"],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        assert "--elastic" in res.stdout, tool


# -- ops_probe --offload ---------------------------------------------------


_OFFLOAD_BLOCK = {
    "enabled": True,
    "demotes": 912, "demote_failed": 0,
    "promotes_host": 640, "promotes_disk": 32,
    "spills": 4, "crc_rejects": 1, "disk_torn": 0,
    "capacity_skips": 2, "host_dropped": 7,
    "host_entries": 233, "host_bytes": 1908736,
    "host_bytes_cap": 67108864,
    "disk_entries": 4, "spill_dir": "/tmp/kv-spill",
    "promote_ms": {"count": 12, "p50": 7.6, "p90": 16.0,
                   "p99": 106.1, "max": 106.1},
}


def test_ops_probe_offload_renders_tier_table(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["offload"] = _OFFLOAD_BLOCK
    statusz["memory"] = {"blocks_evictable": 19,
                         "evictable_bytes": 77824,
                         "pool_bytes": 135168}
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--offload")
    assert res.returncode == 0, res.stdout + res.stderr
    # all three tiers, the crossing counters, and the device pool's
    # reclaimable bytes must appear
    for needle in ("device", "host", "disk", "77824",
                   "demotes=912", "promotes_host=640",
                   "promotes_disk=32", "crc_rejects=1",
                   "capacity_skips=2", "/tmp/kv-spill", "p50=7.6"):
        assert needle in res.stdout, (needle, res.stdout)


def test_ops_probe_offload_gates_on_missing_block(stub_ops):
    res = _probe(stub_ops.server_address[1], "--offload")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "offload" in res.stderr
    _no_traceback(res)


def test_ops_probe_offload_gates_on_disabled_tier(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["offload"] = dict(_OFFLOAD_BLOCK, enabled=False)
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--offload")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "disabled" in res.stderr
    _no_traceback(res)


def test_kv_offload_flags_advertised_by_gating_tools():
    """The build-matrix ``kv_offload`` axis invokes chaos_soak with
    ``--kv-offload`` and ops_probe with ``--offload`` — a dropped flag would fail the axis with an
    argparse error instead of a judged result."""
    for tool, flag in (("chaos_soak.py", "--kv-offload"),
                       ("ops_probe.py", "--offload")):
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / tool), "--help"],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        assert flag in res.stdout, tool


# -- ops_probe --journeys / --journey --------------------------------------


_JOURNEYS_BLOCK = {
    "enabled": True, "started": 12, "finished": 11, "open": 1,
    "hops": 61, "dropped": 0,
    "exemplars": {"ttft": {"20": {"value": 1.5, "rid": 7}},
                  "itl": {"18": {"value": 0.8, "rid": 3}}},
}

_JOURNEY_BODY = {
    "rid": 7, "complete": True, "finish_reason": "eos",
    "replicas": ["router", "replica0", "replica1"],
    "duration": 6.0,
    "hop_counts": {"submit": 1, "route": 1, "enqueue": 2, "admit": 2,
                   "evacuate": 1, "reenqueue": 1, "first_token": 1,
                   "finish": 1},
    "hops": [
        {"rid": 7, "seq": 1, "replica": "router", "iter": 2,
         "t": 2.0, "kind": "submit"},
        {"rid": 7, "seq": 2, "replica": "router", "iter": 2,
         "t": 2.0, "kind": "route", "to": "replica0"},
        {"rid": 7, "seq": 3, "replica": "replica0", "iter": 2,
         "t": 2.0, "kind": "enqueue", "uid": 0},
        {"rid": 7, "seq": 4, "replica": "router", "iter": 4,
         "t": 4.0, "kind": "evacuate", "src": "replica0", "uid": 0},
        {"rid": 7, "seq": 5, "replica": "router", "iter": 4,
         "t": 4.0, "kind": "reenqueue", "to": "replica1", "uid": 0},
        {"rid": 7, "seq": 6, "replica": "replica1", "iter": 8,
         "t": 8.0, "kind": "finish", "reason": "eos", "tokens": 5},
    ],
}


def test_ops_probe_journeys_renders_census_and_exemplars(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["journeys"] = _JOURNEYS_BLOCK
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--journeys")
    assert res.returncode == 0, res.stdout + res.stderr
    # the census counters and the worst-rid-per-bucket exemplar rows
    for needle in ("started=12", "finished=11", "open=1",
                   "dropped=0", "ttft", "itl"):
        assert needle in res.stdout, (needle, res.stdout)
    # the exemplar rid is the whole point of the table
    assert "7" in res.stdout and "1.5" in res.stdout


def test_ops_probe_journeys_gates_on_missing_block(stub_ops):
    res = _probe(stub_ops.server_address[1], "--journeys")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "journeys" in res.stderr
    _no_traceback(res)


def test_ops_probe_journeys_gates_on_disabled_plane(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["journeys"] = dict(_JOURNEYS_BLOCK, enabled=False)
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--journeys")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "disabled" in res.stderr
    _no_traceback(res)


def test_ops_probe_journey_renders_merged_hops(stub_ops):
    stub_ops.journey_body = json.dumps(_JOURNEY_BODY).encode()
    res = _probe(stub_ops.server_address[1], "--journey", "7")
    assert res.returncode == 0, res.stdout + res.stderr
    # the cross-replica path, front-to-back, with detail keys
    for needle in ("rid=7", "complete", "router", "replica0",
                   "replica1", "evacuate", "reenqueue",
                   "src=replica0", "to=replica1", "reason=eos"):
        assert needle in res.stdout, (needle, res.stdout)


def test_ops_probe_journey_gates_on_unknown_rid(stub_ops):
    res = _probe(stub_ops.server_address[1], "--journey", "99")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "/debug/journey/99" in res.stderr
    _no_traceback(res)


def test_journey_flags_advertised_by_gating_tools():
    """The build-matrix ``journey`` axis invokes chaos_soak with
    ``--journeys`` and ops_probe with ``--journeys`` / ``--journey``
    — a dropped flag would fail the axis with an argparse error
    instead of a judged result."""
    for tool, flags in (("chaos_soak.py", ("--journeys",)),
                        ("ops_probe.py", ("--journeys", "--journey"))):
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / tool), "--help"],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        for flag in flags:
            assert flag in res.stdout, (tool, flag)


# -- tools/journey.py ------------------------------------------------------


def _journey_tool(*argv):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "journey.py"), *argv],
        capture_output=True, text=True, timeout=60)


def _journey_bundle(tmp_path, complete=True, dropped=0):
    """A minimal journeys-bearing bundle directory."""
    j = json.loads(json.dumps(_JOURNEY_BODY))
    if not complete:
        # tear the sequence: drop the finish hop
        j["hops"] = j["hops"][:-1]
        j["hop_counts"].pop("finish")
        j["complete"] = False
        j["finish_reason"] = None
    payload = {
        "census": {"enabled": True, "started": 1,
                   "finished": 1 if complete else 0,
                   "open": 0 if complete else 1,
                   "hops": len(j["hops"]), "dropped": dropped,
                   "exemplars": {}},
        "journeys": {"7": j},
    }
    d = tmp_path / "bundle"
    d.mkdir()
    (d / "manifest.json").write_text(json.dumps({"reason": "test"}))
    (d / "journeys.json").write_text(json.dumps(payload))
    return d


def test_journey_tool_assert_complete_passes(tmp_path):
    d = _journey_bundle(tmp_path, complete=True)
    res = _journey_tool(str(d), "--assert-complete")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_journey_tool_assert_complete_gates_on_torn_journey(tmp_path):
    d = _journey_bundle(tmp_path, complete=False)
    res = _journey_tool(str(d), "--assert-complete")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "incomplete" in res.stderr
    _no_traceback(res)


def test_journey_tool_assert_complete_gates_on_drops(tmp_path):
    d = _journey_bundle(tmp_path, complete=True, dropped=3)
    res = _journey_tool(str(d), "--assert-complete")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "dropped" in res.stderr
    _no_traceback(res)


def test_journey_tool_rid_and_slowest_render(tmp_path):
    d = _journey_bundle(tmp_path)
    res = _journey_tool(str(d), "--rid", "7")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "evacuate" in res.stdout and "replica1" in res.stdout
    res = _journey_tool(str(d), "--slowest", "3")
    assert res.returncode == 0
    assert "complete" in res.stdout
    res = _journey_tool(str(d), "--rid", "999")
    assert res.returncode == 1 and "FAIL" in res.stderr
    _no_traceback(res)


def test_journey_tool_gates_on_journeyless_bundle(tmp_path):
    d = tmp_path / "plain"
    d.mkdir()
    (d / "manifest.json").write_text(json.dumps({"reason": "test"}))
    res = _journey_tool(str(d), "--assert-complete")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "journeys.json" in res.stderr
    _no_traceback(res)
    res = _journey_tool(str(tmp_path / "nowhere"))
    assert res.returncode == 1 and "FAIL" in res.stderr
    _no_traceback(res)


# -- obs_dump --------------------------------------------------------------


def _dump(*argv):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "obs_dump.py"), *argv],
        capture_output=True, text=True, timeout=60)


def _trace_file(tmp_path, names=("launch", "retire")):
    events = []
    for i, name in enumerate(names):
        events.append({"ph": "B", "name": name, "pid": 1, "tid": 1,
                       "ts": i * 10.0})
        events.append({"ph": "E", "name": name, "pid": 1, "tid": 1,
                       "ts": i * 10.0 + 5.0})
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"traceEvents": events}))
    return path


def test_obs_dump_require_present_passes(tmp_path):
    res = _dump("trace", str(_trace_file(tmp_path)),
                "--require", "launch", "--require", "retire")
    assert res.returncode == 0, res.stdout + res.stderr


def test_obs_dump_require_missing_gates(tmp_path):
    res = _dump("trace", str(_trace_file(tmp_path)),
                "--require", "launch", "--require", "no_such_span")
    assert res.returncode == 1
    assert "no_such_span" in res.stderr and "FAIL" in res.stderr
    _no_traceback(res)


def test_obs_dump_clean_exit_on_missing_file(tmp_path):
    for sub in ("trace", "metrics"):
        res = _dump(sub, str(tmp_path / "nope.json"))
        assert res.returncode == 1
        assert "FAIL" in res.stderr and "cannot read" in res.stderr
        _no_traceback(res)


def test_obs_dump_clean_exit_on_malformed_artifacts(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    res = _dump("trace", str(bad))
    assert res.returncode == 1 and "FAIL" in res.stderr
    _no_traceback(res)
    jl = tmp_path / "bad.jsonl"
    jl.write_text('{"ts": 1, "metrics": {}}\n{oops\n')
    res = _dump("metrics", jl.as_posix())
    assert res.returncode == 1 and "not JSON" in res.stderr
    _no_traceback(res)
    scalar = tmp_path / "scalar.json"
    scalar.write_text('"just a string"')
    res = _dump("trace", str(scalar))
    assert res.returncode == 1 and "traceEvents" in res.stderr
    _no_traceback(res)


def test_obs_dump_empty_metrics_file_gates(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    res = _dump("metrics", str(empty))
    assert res.returncode == 1
    _no_traceback(res)


def test_obs_dump_merges_replica_traces_onto_distinct_tids(tmp_path):
    """Per-replica tracers in one process stamp the SAME (pid, tid)
    — the multi-path trace mode must renamespace them so Perfetto
    gets one track per (replica, thread) with a naming metadata
    event, and --require judges the union."""
    a = _trace_file(tmp_path, names=("launch",))
    b = tmp_path / "b.json"
    b.write_text(json.dumps({"traceEvents": [
        {"ph": "B", "name": "retire", "pid": 1, "tid": 1, "ts": 0.0},
        {"ph": "E", "name": "retire", "pid": 1, "tid": 1, "ts": 5.0},
    ]}))
    out = tmp_path / "merged.json"
    res = _dump("trace", str(a), str(b), "--merge", str(out),
                "--require", "launch", "--require", "retire")
    assert res.returncode == 0, res.stdout + res.stderr
    merged = json.loads(out.read_text())["traceEvents"]
    real = [ev for ev in merged if ev["ph"] != "M"]
    metas = [ev for ev in merged if ev["ph"] == "M"]
    # colliding (pid=1, tid=1) from the two files land on two tracks
    assert {ev["tid"] for ev in real} == {0, 1}
    assert sorted(ev["args"]["name"] for ev in metas) == \
        ["replica0/tid1", "replica1/tid1"]
    # a single path stays un-renamespaced (byte-identical summaries)
    res = _dump("trace", str(a))
    assert res.returncode == 0
    assert str(a) + ":" in res.stdout


# -- ops_probe --transport -------------------------------------------------


_TRANSPORT_BLOCK = {
    "backend": "inprocess", "peers": 2, "attempts": 38,
    "retries": 11, "delivered": 21, "rejects": 5, "failures": 1,
    "deadline_exceeded": 1, "breaker_fastfail": 0, "ingested": 21,
    "dedup_hits": 16,
    "per_peer": {
        "offload": {"attempts": 30, "retries": 9, "delivered": 17,
                    "rejects": 4, "failures": 1,
                    "deadline_exceeded": 1, "breaker_fastfail": 0,
                    "ingested": 17, "dedup_hits": 12,
                    "breaker": "closed"},
        "replica1": {"attempts": 8, "retries": 2, "delivered": 4,
                     "rejects": 1, "failures": 0,
                     "deadline_exceeded": 0, "breaker_fastfail": 0,
                     "ingested": 4, "dedup_hits": 4,
                     "breaker": "open"},
    },
}


def test_ops_probe_transport_renders_per_peer_table(stub_ops):
    statusz = dict(_STATUSZ)
    statusz["transport"] = _TRANSPORT_BLOCK
    stub_ops.statusz_body = json.dumps(statusz).encode()
    res = _probe(stub_ops.server_address[1], "--transport")
    assert res.returncode == 0, res.stdout + res.stderr
    # backend, totals, both peers, and each peer's breaker state
    for needle in ("backend=inprocess", "attempts=38",
                   "dedup_hits=16", "deadline_exceeded=1",
                   "offload", "replica1", "closed", "open"):
        assert needle in res.stdout, (needle, res.stdout)


def test_ops_probe_transport_gates_on_missing_block(stub_ops):
    res = _probe(stub_ops.server_address[1], "--transport")
    assert res.returncode == 1
    assert "FAIL" in res.stderr and "transport" in res.stderr
    _no_traceback(res)


def test_transport_flags_advertised_by_gating_tools():
    """The build-matrix ``transport`` axis invokes chaos_soak with
    ``--transport-faults`` and ops_probe with ``--transport`` — a
    dropped flag would fail the
    axis with an argparse error instead of a judged result."""
    for tool, flag in (("chaos_soak.py", "--transport-faults"),
                       ("ops_probe.py", "--transport")):
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / tool), "--help"],
            capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        assert flag in res.stdout, tool
