"""Embedded HTTP ops plane for a live :class:`InferenceServer`.

Everything the observability stack accumulated so far —
``stats()``, ``prometheus_text()``, the flight ring, per-request
timelines, postmortem bundles — was reachable only by code already
holding the server object.  The ops plane puts those signals on the
wire: a dependency-free stdlib ``http.server`` on a daemon thread,
loopback-bound, OFF by default (``ops_port=`` or
``APEX_TPU_OPS_PORT``; port 0 binds an ephemeral port, readable back
from :attr:`OpsServer.port`).  This is what the ROADMAP's
multi-replica front door scrapes to load-balance and fail over — and
what an operator curls at 3am.

Endpoints:

- ``GET /healthz`` — liveness/readiness in one probe: 200
  ``{"status": "ok"}`` on a healthy server, 503 with ``"draining"``
  / ``"breaker_open"`` / ``"stalled"`` (watchdog) / ``"closed"``
  otherwise, so a router can pull the replica on status code alone.
  Deliberately **lock-free** (plain attribute reads): the one moment
  health must answer is while the serve loop is wedged holding the
  ops lock.
- ``GET /metrics`` — ``MetricsRegistry.prometheus_text()`` under the
  proper ``text/plain; version=0.0.4`` content type (scrapers key on
  it).  Also lock-free: a scrape must not block behind a slow step.
- ``GET /statusz`` — the full ``stats()`` JSON (programs table,
  watchdog, SLO, memory, ...), serialized against the step loop.
- ``GET /debug/flight?n=N`` — the flight-recorder tail as JSONL
  (empty with the null recorder).
- ``GET /debug/requests/<uid>`` — one request's ``timeline()`` (the
  slice ``tools/postmortem.py --request`` renders from bundles, but
  live) plus its current state; 404 for unknown uids.
- ``GET /debug/journey/<rid>`` — one request's merged cross-replica
  journey (``docs/observability.md``, "Request journeys &
  exemplars"); 409 when journeys are disabled, 404 for unknown rids.
- ``GET /metrics/fleet`` — fleet-wide Prometheus exposition with a
  ``replica=<name>`` label per replica series (fleet ops plane only;
  404 on a single server's).
- ``POST /drain`` / ``POST /postmortem`` — authenticated-by-loopback
  triggers into :meth:`InferenceServer.drain` /
  :meth:`~InferenceServer.dump_postmortem` (non-loopback peers get
  403; the listener is loopback-bound anyway — defense in depth).
- ``POST /generate`` + ``GET /stream/<id>`` — the streaming front
  door (``docs/serving.md``, "Streaming & cancellation"): the POST
  submits ``{"prompt": [...], "max_new_tokens": N, ...}`` and
  returns the stream id; the GET serves that request's tokens as
  Server-Sent Events (``event: token`` per retired token, one
  ``event: end`` carrying the ``finish_reason``).  The SSE loop
  blocks on the stream broker's OWN lock — never the ops lock — and
  a broken client socket **cancels** the request
  (``finish_reason="cancelled"``), freeing its blocks mid-decode.
  Hosted by both a single server's ops plane and the fleet's
  aggregate one (``RouterFleet(ops_port=)`` — streams there survive
  failover and hand-off).

Mutating reads (``/statusz``, ``/debug/*``) and the POST triggers
serialize against the serve loop through :attr:`OpsServer.lock` —
``InferenceServer.step()`` holds it per iteration *only while an ops
plane is attached*, so servers without one pay nothing.  Request
handling is bounded: loopback bind, per-connection socket timeout,
a request-body cap, and one-shot HTTP/1.0 connections.

``tools/ops_probe.py`` is the CLI client (poll, ``--assert-healthy``
gate, program-table rendering).  See ``docs/observability.md``,
"Ops plane & watchdog".
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple
from urllib.parse import parse_qs, urlparse

from apex_tpu.observability.registry import PROMETHEUS_CONTENT_TYPE

OPS_PORT_ENV = "APEX_TPU_OPS_PORT"

_LOOPBACK = ("127.0.0.1", "::1", "::ffff:127.0.0.1")

# one request body bound — the POST triggers carry no payload, so
# anything large is abuse, not traffic
_MAX_BODY = 64 * 1024


class _Handler(BaseHTTPRequestHandler):
    """Routes every request through the owning :class:`OpsServer`."""

    timeout = 10.0            # per-connection socket budget (bounded)

    def do_GET(self):         # noqa: N802 — http.server API
        self.server.ops._handle(self, "GET")

    def do_POST(self):        # noqa: N802
        self.server.ops._handle(self, "POST")

    def log_message(self, fmt, *args):
        pass                  # counted in the registry, not stderr


class OpsServer:
    """The embedded ops endpoint for one ``InferenceServer``.

    Args:
      server: the (duck-typed) ``InferenceServer`` to expose.
      port: TCP port on loopback; 0 binds an ephemeral port
        (:attr:`port` holds the real one).
      host: bind address — loopback by default and by intent.
      clock: injectable seconds source for ``/healthz`` uptime
        (default: the serving server's own clock).
      counters: optional ``CounterMeter`` (label ``endpoint``)
        counting handled requests into the shared registry.
    """

    def __init__(self, server, *, port: int = 0,
                 host: str = "127.0.0.1", clock=None, counters=None):
        self.server = server
        self.lock = threading.RLock()
        self.counters = counters
        self._clock = clock if clock is not None else server.clock
        self._started_at = self._clock()
        # SSE heartbeat cadence: bounds both disconnect detection and
        # how long a stream handler can block between wakeups
        self._sse_ping_s = 10.0
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.ops = self
        self.host, self.port = self._httpd.server_address[:2]
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "OpsServer":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                kwargs={"poll_interval": 0.2},
                name="apex-tpu-ops", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        t, self._thread = self._thread, None
        if t is not None:
            self._httpd.shutdown()
            t.join(timeout=5.0)
        self._httpd.server_close()

    # -- routing -----------------------------------------------------------

    def _handle(self, h: BaseHTTPRequestHandler, method: str) -> None:
        url = urlparse(h.path)
        path, query = url.path.rstrip("/") or "/", parse_qs(url.query)
        try:
            if method == "GET":
                if path == "/healthz":
                    return self._count_send(h, "healthz",
                                            *self._healthz())
                if path == "/metrics":
                    # apexlint: disable=lock-discipline — documented lock-free: the registry serializes internally and a scrape must not block behind a wedged step
                    text = self.server.registry.prometheus_text()
                    return self._count_send(
                        h, "metrics", 200, text.encode(),
                        PROMETHEUS_CONTENT_TYPE)
                if path == "/metrics/fleet":
                    return self._metrics_fleet(h)
                if path == "/statusz":
                    with self.lock:
                        stats = self.server.stats()
                    return self._count_send(h, "statusz",
                                            *_json(200, stats))
                if path == "/debug/flight":
                    return self._count_send(h, "debug_flight",
                                            *self._flight(query))
                if path.startswith("/debug/requests/"):
                    return self._count_send(
                        h, "debug_requests",
                        *self._request(path.rsplit("/", 1)[1]))
                if path.startswith("/debug/journey/"):
                    return self._count_send(
                        h, "debug_journey",
                        *self._journey(path.rsplit("/", 1)[1]))
                if path.startswith("/stream/"):
                    return self._stream(h, path.rsplit("/", 1)[1])
            elif method == "POST":
                if h.client_address[0] not in _LOOPBACK:
                    return self._count_send(h, "forbidden", *_json(
                        403, {"error": "loopback only"}))
                body = self._read_body(h)
                if body is None:
                    return self._count_send(h, "too_large", *_json(
                        413, {"error": "request body too large"}))
                if path == "/drain":
                    return self._count_send(h, "drain",
                                            *self._drain())
                if path == "/postmortem":
                    return self._count_send(h, "postmortem",
                                            *self._postmortem())
                if path == "/generate":
                    return self._count_send(h, "generate",
                                            *self._generate(body))
            self._count_send(h, "unknown", *_json(
                404, {"error": f"no such endpoint: {method} {path}"}))
        except (BrokenPipeError, ConnectionResetError):
            pass              # client went away mid-reply; nothing owed
        except Exception as e:  # noqa: BLE001 — a handler bug must
            #                     not kill the ops thread pool
            try:
                self._count_send(h, "error",
                                 *_json(500, {"error": repr(e)}))
            except OSError:
                pass

    def _count_send(self, h, endpoint: str, code: int, body: bytes,
                    content_type: str) -> None:
        if self.counters is not None:
            self.counters.incr(endpoint)
        h.send_response(code)
        h.send_header("Content-Type", content_type)
        h.send_header("Content-Length", str(len(body)))
        h.end_headers()
        h.wfile.write(body)

    @staticmethod
    def _read_body(h) -> Optional[bytes]:
        """Bounded body read; None = over the cap (413)."""
        n = int(h.headers.get("Content-Length") or 0)
        if n > _MAX_BODY:
            return None
        return h.rfile.read(n) if n else b""

    # -- endpoint bodies ---------------------------------------------------

    # apexlint: disable=lock-discipline — documented lock-free contract: health MUST answer while the serve loop is wedged holding the ops lock
    def _healthz(self) -> Tuple[int, bytes, str]:
        """Lock-free health: readable even while the serve loop is
        wedged inside a step holding the ops lock."""
        srv = self.server
        if srv.watchdog.stalled:
            status = "stalled"
        elif srv.closed:
            status = "closed"
        elif srv.draining:
            status = "draining"
        elif srv.breaker.state == "open":
            status = "breaker_open"
        else:
            status = "ok"
        sched = srv.scheduler
        body = {
            "status": status,
            "iter": srv._iter,
            "breaker": srv.breaker.state,
            "pressure": round(srv.pressure_gauge.val, 4),
            # the router-scrape trio (docs/serving.md, "Multi-replica
            # routing"): one cheap machine-readable probe carries the
            # placement signal (pressure), the lifecycle flag
            # (draining), and the occupancy (waiting + running) a
            # balancer keys on — no /statusz parse needed.  Plain
            # attribute reads, same lock-free contract as the rest of
            # this body.
            "draining": bool(srv.draining),
            "live_requests": len(sched.waiting) + len(sched.running),
            "watchdog_stalls": srv.watchdog.stalls,
            "uptime_s": round(self._clock() - self._started_at, 3),
        }
        # streaming gauges ride the same probe (broker-locked, not
        # ops-locked — still safe while the serve loop is wedged)
        broker = getattr(srv, "stream_broker", None)
        body["active_streams"] = (broker.active
                                  if broker is not None else 0)
        body["stream_backpressure_drops"] = (
            broker.backpressure_drops if broker is not None else 0)
        return _json(200 if status == "ok" else 503, body)

    def _flight(self, query) -> Tuple[int, bytes, str]:
        try:
            n = int(query.get("n", ["50"])[0])
        except ValueError:
            return _json(400, {"error": "n must be an integer"})
        with self.lock:
            records = self.server.recorder.records()
        tail = records[-n:] if n > 0 else ()
        body = "".join(json.dumps(r, sort_keys=True) + "\n"
                       for r in tail)
        return 200, body.encode(), "application/jsonl; charset=utf-8"

    def _request(self, uid_text: str) -> Tuple[int, bytes, str]:
        try:
            uid = int(uid_text)
        except ValueError:
            return _json(400, {"error": f"bad uid: {uid_text!r}"})
        with self.lock:
            sched = self.server.scheduler
            req, state = None, None
            for r in sched.finished:
                if r.uid == uid:
                    req, state = r, "finished"
                    break
            if req is None:
                r = sched.running.get(uid)
                if r is not None:
                    req, state = r, "running"
            if req is None:
                for r in sched.waiting:
                    if r.uid == uid:
                        req, state = r, "waiting"
                        break
            if req is None:
                return _json(404, {"error": f"unknown request {uid}"})
            body = {"state": state, "timeline": req.timeline()}
        return _json(200, body)

    def _metrics_fleet(self, h) -> None:
        """Fleet-wide exposition (``fleet_metrics_text``): every
        replica's series under a ``replica=<name>`` label in one
        conformant page.  404 on a single server's ops plane — the
        plain ``/metrics`` already is the whole story there."""
        fm = getattr(self.server, "fleet_metrics_text", None)
        if fm is None:
            return self._count_send(h, "metrics_fleet", *_json(
                404, {"error": "not a fleet ops plane"}))
        # apexlint: disable=lock-discipline — documented lock-free: same scrape contract as /metrics (the registries serialize internally)
        text = fm()
        return self._count_send(h, "metrics_fleet", 200,
                                text.encode(),
                                PROMETHEUS_CONTENT_TYPE)

    def _journey(self, rid_text: str) -> Tuple[int, bytes, str]:
        """One request's merged journey (``docs/observability.md``,
        "Request journeys & exemplars"): the fleet ops plane merges
        hops across every replica the rid touched; a single server's
        serves its local log.  409 when the correlation plane is not
        armed — distinct from 404 (armed, rid unknown), so a prober
        can tell "turn it on" from "no such request"."""
        try:
            rid = int(rid_text)
        except ValueError:
            return _json(400, {"error": f"bad rid: {rid_text!r}"})
        jlog = getattr(self.server, "journeys", None)
        if jlog is None or not jlog.enabled:
            return _json(409, {"error": "journeys disabled "
                                        "(enable_journeys=False)"})
        with self.lock:
            j = self.server.journey(rid)
        if j is None:
            return _json(404, {"error": f"unknown journey rid {rid}"})
        return _json(200, j)

    def _drain(self) -> Tuple[int, bytes, str]:
        with self.lock:
            stats = self.server.drain()
        return _json(200, {
            "status": "drained",
            "requests_finished": stats["requests_finished"]})

    # -- streaming front door (docs/serving.md) ----------------------------

    def _generate(self, body: bytes) -> Tuple[int, bytes, str]:
        """Submit one request from a JSON body; returns the id to
        ``GET /stream/<id>`` (the router-level ``rid`` on a fleet ops
        plane, the request ``uid`` on a single server's)."""
        try:
            payload = json.loads(body or b"{}")
            prompt = [int(t) for t in payload["prompt"]]
            max_new = int(payload["max_new_tokens"])
        except (ValueError, TypeError, KeyError) as e:
            return _json(400, {"error": f"bad generate body: {e!r}"})
        eos_id = payload.get("eos_id")
        priority = int(payload.get("priority", 0))
        srv = self.server
        if getattr(srv, "stream_broker", None) is None:
            return _json(409, {"error": "streaming disabled "
                                        "(enable_streaming=False)"})
        try:
            # apexlint: disable=lock-discipline — documented lock-free: submit() takes the ops lock itself (both server kinds); taking self.lock here would deadlock a non-reentrant configuration and serialize admission behind slow scrapes
            req = srv.submit(prompt, max_new,
                             eos_id if eos_id is None else int(eos_id),
                             priority=priority)
        except (ValueError, TypeError, RuntimeError) as e:
            return _json(400, {"error": str(e)})
        sid = getattr(req, "rid", None)
        if sid is None:
            sid = req.uid
        out = {"id": sid, "finished": bool(req.finished)}
        if req.finished:       # turned away at the front door
            out["finish_reason"] = req.finish_reason
        return _json(200, out)

    def _stream(self, h, id_text: str) -> None:
        """Serve one request's tokens as SSE.  The setup (stream
        lookup) serializes on the ops lock; the delivery loop blocks
        only on the broker's own condition variable, so a slow or
        stalled consumer thread never holds the ops lock.  A broken
        client socket cancels the request — the disconnect-
        cancellation contract the chaos soak fires faults at."""
        try:
            sid = int(id_text)
        except ValueError:
            return self._count_send(h, "stream", *_json(
                400, {"error": f"bad stream id: {id_text!r}"}))
        srv = self.server
        if getattr(srv, "stream_broker", None) is None:
            return self._count_send(h, "stream", *_json(
                409, {"error": "streaming disabled"}))
        try:
            # apexlint: disable=lock-discipline — documented lock-free: stream() takes the ops lock itself; the delivery loop below must NOT hold self.lock (it blocks on the broker condition for seconds at a time)
            stream = srv.stream(sid)
        except KeyError:
            return self._count_send(h, "stream", *_json(
                404, {"error": f"unknown stream id {sid}"}))
        if self.counters is not None:
            self.counters.incr("stream")
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-cache")
        h.end_headers()
        try:
            while True:
                toks = stream.take(timeout=self._sse_ping_s)
                for tok in toks:
                    h.wfile.write(
                        f"event: token\ndata: {tok}\n\n".encode())
                if stream.done:
                    h.wfile.write(
                        f"event: end\ndata: "
                        f"{stream.finish_reason}\n\n".encode())
                    h.wfile.flush()
                    return
                if not toks:
                    # heartbeat comment: the only way a one-way SSE
                    # pipe learns the client hung up between tokens
                    h.wfile.write(b": ping\n\n")
                h.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # client disconnected mid-stream: free its blocks NOW
            stream.close()
            # apexlint: disable=lock-discipline — documented lock-free: cancel() takes the ops lock itself; holding self.lock across it would nest the locks in the opposite order of /statusz
            srv.cancel(sid)

    def _postmortem(self) -> Tuple[int, bytes, str]:
        """Bundle-path choice AND the dump run under one lock hold:
        picking the name from an unlocked ``_iter`` read raced the
        step loop (apexlint lock-discipline) and left a TOCTOU
        between the exists() scan and the write."""
        srv = self.server
        with self.lock:
            base = srv._postmortem_dir or tempfile.gettempdir()
            path = os.path.join(base,
                                f"ops_postmortem_iter{srv._iter}")
            i = 1
            while os.path.exists(path):
                path = os.path.join(
                    base, f"ops_postmortem_iter{srv._iter}_{i}")
                i += 1
            manifest = srv.dump_postmortem(path, reason="ops_request")
        return _json(200, {"path": path, "manifest": manifest})


def _json(code: int, payload) -> Tuple[int, bytes, str]:
    body = json.dumps(payload, sort_keys=True, default=str).encode()
    return code, body, "application/json; charset=utf-8"
