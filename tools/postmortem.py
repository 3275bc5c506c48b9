"""Render, slice, gate, and diff serving postmortem bundles.

A bundle (``apex_tpu.observability.write_postmortem``,
``docs/observability.md`` "Flight recorder & postmortems") is a
directory of four cross-reconciling files: ``manifest.json``,
``flight.jsonl`` (one structured record per engine step),
``metrics.json`` (a full ``MetricsRegistry.snapshot()``), and
``trace.json`` (Chrome trace).  ``InferenceServer`` writes them on
demand (``dump_postmortem``), on breaker-open transitions and
``audit()`` failures, and ``resilience.chaos.run_soak`` writes one on
any invariant violation.

Modes:

``BUNDLE``
    Render the manifest header plus a step table (newest last;
    ``--last-n-steps N`` bounds it, default 10): iteration, tokens
    produced, queue/batch composition, pressure, breaker state, and
    memory occupancy per step, with admit/shed/finish decisions
    called out.  A watchdog-triggered bundle
    (``reason="watchdog_stall"``) additionally renders the stall
    (where it hung, for how long, against what deadline) and the
    head of the attached thread-stack dump — the wedged serve
    thread's frames are the point of the capture.

``BUNDLE --request UID``
    The per-request step slice: only the steps in which request
    ``UID`` appears (admitted / running / prefilling / shed /
    finished), reconstructing its admit → ... → finish path.

``BUNDLE --assert-complete``
    The build-matrix gate: every file parses, the step accounting in
    the manifest reconciles with the flight log AND with the metrics
    snapshot's step counters, iterations are strictly increasing,
    per-request events are consistent (at most one finish per uid;
    admit precedes finish; nothing runs before its admission when the
    ring dropped nothing), and the trace is structurally valid.  A
    watchdog bundle must additionally carry its stall record and a
    non-empty thread-stack attachment (the ``opsplane`` build-matrix
    axis gates a forced hang through this).  Exit 1 with the failing
    check otherwise.

``BUNDLE --diff OTHER``
    Metrics delta between two bundles (``snapshot_diff`` semantics:
    counter/histogram increments, gauge values, reset flags) plus the
    step-count delta — "what moved between these two captures".

Usage:
    python tools/postmortem.py /tmp/pm/invariant_violation
    python tools/postmortem.py BUNDLE --request 17 --last-n-steps 50
    python tools/postmortem.py BUNDLE --assert-complete
    python tools/postmortem.py BUNDLE_A --diff BUNDLE_B
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.observability.flightrecorder import (  # noqa: E402
    FLIGHT_NAME,
    MANIFEST_NAME,
    METRICS_NAME,
    TRACE_NAME,
)
from apex_tpu.observability.registry import snapshot_diff  # noqa: E402


class BundleError(Exception):
    """A bundle file is missing or unparseable."""


def load_bundle(dirpath: str) -> dict:
    """Parse all four members; raises :class:`BundleError` naming the
    offending file."""
    out = {"dir": dirpath}
    for key, name in (("manifest", MANIFEST_NAME),
                      ("metrics", METRICS_NAME), ("trace", TRACE_NAME)):
        path = os.path.join(dirpath, name)
        try:
            with open(path) as f:
                out[key] = json.load(f)
        except (OSError, ValueError) as e:
            raise BundleError(f"{path}: {e}")
    # a watchdog bundle names a thread-stack attachment in its
    # manifest extra; load it alongside (None when absent/named-but-
    # missing — assert_complete turns the latter into a failure)
    out["threads"] = None
    attach = (out["manifest"].get("extra") or {}).get("thread_stacks")
    if attach:
        try:
            with open(os.path.join(dirpath, os.path.basename(attach))) as f:
                out["threads"] = f.read()
        except OSError:
            out["threads"] = None
    path = os.path.join(dirpath, FLIGHT_NAME)
    steps = []
    try:
        with open(path) as f:
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if line:
                    try:
                        steps.append(json.loads(line))
                    except ValueError as e:
                        raise BundleError(f"{path}:{ln}: {e}")
    except OSError as e:
        raise BundleError(f"{path}: {e}")
    out["steps"] = steps
    return out


def request_events(steps):
    """uid -> ordered [(iter, event)] with event one of ``admitted`` /
    ``running`` / ``prefilling`` / ``shed`` / ``finished:<reason>`` —
    the per-request reconstruction behind ``--request`` and
    ``--assert-complete``."""
    ev = {}

    def note(uid, i, what):
        ev.setdefault(uid, []).append((i, what))

    for rec in steps:
        i = rec.get("iter")
        for uid in rec.get("admitted", ()):
            note(uid, i, "admitted")
        for uid in rec.get("prefilling", ()):
            note(uid, i, "prefilling")
        for uid in rec.get("running", ()):
            note(uid, i, "running")
        for s in rec.get("shed", ()):
            note(s["uid"], i, "shed")
        for f in rec.get("finished", ()):
            note(f["uid"], i, f"finished:{f.get('reason')}")
    return ev


# launch families for the phase-composition reconcile: flight "phase"
# launch counters vs the serving_program_calls{program=...} counters
# (logits + sampled + stochastic twins count together, exactly like
# the engine's compile audits; width keys like "chunk_prefill[64q8]"
# strip to their family)
_PHASE_FAMILIES = {
    "prefill_launches": ("chunk_prefill", "chunk_prefill_sampled",
                         "chunk_prefill_stoch"),
    "decode_launches": ("decode", "decode_sampled", "decode_stoch"),
    "verify_launches": ("verify", "verify_sampled", "verify_stoch"),
}


def _phase_cell(rec) -> str:
    """Compact phase-composition cell: prefill tokens / decode tokens
    / verify columns this step (the interference view)."""
    ph = rec.get("phase")
    if not isinstance(ph, dict):
        return ""
    parts = []
    if ph.get("prefill_tokens"):
        parts.append(f"pf:{ph['prefill_tokens']}")
    if ph.get("decode_tokens"):
        parts.append(f"dec:{ph['decode_tokens']}")
    if ph.get("verify_columns"):
        parts.append(f"ver:{ph['verify_columns']}")
    if ph.get("handoff_blocks"):
        parts.append(f"hof:{ph['handoff_blocks']}")
    return "+".join(parts) if parts else "idle"


def _step_row(rec) -> str:
    mem = rec.get("memory", {})
    decisions = []
    cell = _phase_cell(rec)
    if cell:
        decisions.append(f"phase={cell}")
    if rec.get("admitted"):
        decisions.append(f"admit={rec['admitted']}")
    if rec.get("shed"):
        decisions.append(
            "shed=" + str([s["uid"] for s in rec["shed"]]))
    if rec.get("preemptions"):
        decisions.append(f"preempt={rec['preemptions']}")
    if rec.get("evicted_blocks"):
        decisions.append(f"evict={rec['evicted_blocks']}")
    if rec.get("oom"):
        decisions.append(f"oom={rec['oom']}")
    if rec.get("finished"):
        decisions.append(
            "finish=" + str([(f["uid"], f.get("reason"))
                             for f in rec["finished"]]))
    return (f"{rec.get('iter', '?'):>6} {rec.get('produced', 0):>4} "
            f"{rec.get('waiting', 0):>4} {len(rec.get('running', ())):>3} "
            f"{rec.get('pressure', 0.0):>6.2f} "
            f"{rec.get('breaker', '?'):<9} "
            f"{mem.get('live', 0):>4}/{mem.get('free', 0):<4} "
            f"{' '.join(decisions)}")


def render(bundle, args) -> int:
    man = bundle["manifest"]
    print(f"{bundle['dir']}: reason={man.get('reason')!r} "
          f"steps={man.get('steps_in_bundle')} "
          f"(recorded={man.get('steps_recorded')}, "
          f"dropped={man.get('steps_dropped')})")
    extra = man.get("extra")
    if extra:
        print(f"  extra: {json.dumps(extra, sort_keys=True)}")
    if man.get("reason") == "watchdog_stall":
        stall = (extra or {}).get("stall", {})
        print(f"  watchdog stall: where={stall.get('where')} "
              f"age={stall.get('age_s')}s "
              f"deadline={stall.get('deadline_s')}s "
              f"(stall #{stall.get('stalls')})")
        threads = bundle.get("threads")
        if threads:
            lines = threads.splitlines()
            print(f"  thread stacks ({len(lines)} lines; "
                  f"{(extra or {}).get('thread_stacks')}):")
            for ln in lines[:8]:
                print(f"    {ln}")
            if len(lines) > 8:
                print(f"    ... {len(lines) - 8} more lines")
        else:
            print("  thread stacks: MISSING", file=sys.stderr)
    steps = bundle["steps"]
    if args.request is not None:
        ev = request_events(steps).get(args.request)
        if not ev:
            print(f"request {args.request}: not in the recorded window",
                  file=sys.stderr)
            return 1
        print(f"\nrequest {args.request} path "
              f"({len(ev)} events):")
        for i, what in ev:
            print(f"  iter {i:>6}: {what}")
        uids = {args.request}
        steps = [r for r in steps
                 if args.request in r.get("admitted", ())
                 or args.request in r.get("running", ())
                 or args.request in r.get("prefilling", ())
                 or any(s["uid"] in uids for s in r.get("shed", ()))
                 or any(f["uid"] in uids
                        for f in r.get("finished", ()))]
    if args.last_n_steps is not None:
        steps = steps[-args.last_n_steps:]
    if steps:
        print(f"\n{'iter':>6} {'tok':>4} {'wait':>4} {'run':>3} "
              f"{'press':>6} {'breaker':<9} {'live/free':<9} decisions")
        for rec in steps:
            print(_step_row(rec))
    return 0


def assert_complete(bundle) -> int:
    """The ``--assert-complete`` gate; prints the first failing check
    and returns 1, else 0."""
    man, steps, metrics = (bundle["manifest"], bundle["steps"],
                           bundle["metrics"])

    def fail(msg: str) -> int:
        print(f"FAIL: {bundle['dir']}: {msg}", file=sys.stderr)
        return 1

    if len(steps) != man.get("steps_in_bundle"):
        return fail(f"flight.jsonl holds {len(steps)} steps, manifest "
                    f"says {man.get('steps_in_bundle')}")
    if man.get("steps_recorded") != \
            man.get("steps_in_bundle") + man.get("steps_dropped"):
        return fail("manifest step accounting does not add up: "
                    f"{man.get('steps_recorded')} != "
                    f"{man.get('steps_in_bundle')} + "
                    f"{man.get('steps_dropped')}")
    iters = [rec.get("iter") for rec in steps]
    if any(not isinstance(i, int) for i in iters):
        return fail("a step record has no integer 'iter'")
    if any(b <= a for a, b in zip(iters, iters[1:])):
        return fail("step iterations are not strictly increasing")
    # cross-reconcile with the metrics snapshot: the recorder and the
    # serving_step_s histogram both see every step exactly once
    step_hist = metrics.get("serving_step_s")
    if step_hist is not None and \
            step_hist.get("count") != man.get("steps_recorded"):
        return fail(f"recorder saw {man.get('steps_recorded')} steps "
                    f"but serving_step_s counted "
                    f"{step_hist.get('count')}")
    # per-request consistency: one finish per uid, admit before finish,
    # and (with a complete window) nothing runs before its admission
    complete = man.get("steps_dropped") == 0
    for uid, ev in request_events(steps).items():
        finishes = [(i, w) for i, w in ev if w.startswith("finished:")]
        if len(finishes) > 1:
            return fail(f"request {uid} finished "
                        f"{len(finishes)} times: {finishes}")
        admits = [i for i, w in ev if w == "admitted"]
        if finishes and admits and min(admits) > finishes[0][0]:
            return fail(f"request {uid} admitted at iter "
                        f"{min(admits)} after finishing at "
                        f"{finishes[0][0]}")
        if complete:
            runs = [i for i, w in ev if w in ("running", "prefilling")]
            if runs and not admits:
                return fail(f"request {uid} runs at iter {min(runs)} "
                            f"with no admission in a complete window")
    # phase-composition reconcile: when the window is complete from
    # the server's first step AND every record carries a phase block,
    # the per-family launch counts summed over the flight log must
    # equal the per-program call counters in the metrics snapshot —
    # the recorder and the program accounting each saw every launch
    # exactly once (docs/observability.md)
    if (complete and steps and steps[0].get("iter") == 1
            and all(isinstance(r.get("phase"), dict) for r in steps)):
        prog_calls = {}
        prefix = "serving_program_calls{"
        for key, desc in metrics.items():
            if not key.startswith(prefix):
                continue
            prog = key[len(prefix):].split("=", 1)[-1].strip('"}')
            prog_calls.setdefault(prog.split("[")[0], 0)
            prog_calls[prog.split("[")[0]] += desc.get("value", 0)
        # a bundle dumped from inside a step (a watchdog stall) lacks
        # that step's record, whose launches are already counted
        stall = (man.get("extra") or {}).get("stall") or {}
        in_step = stall.get("where") == "in_step"
        for field, families in _PHASE_FAMILIES.items():
            flight_n = sum(r["phase"].get(field, 0) for r in steps)
            metric_n = sum(prog_calls.get(f, 0) for f in families)
            if prog_calls and (flight_n > metric_n if in_step
                               else flight_n != metric_n):
                return fail(
                    f"phase split does not reconcile: flight counts "
                    f"{flight_n} {field} but the program counters "
                    f"saw {metric_n} ({'+'.join(families)})")
    # watchdog bundles: the stall record and the thread-stack
    # attachment are the capture's payload — a bundle without them is
    # a detector that fired blind
    if man.get("reason") == "watchdog_stall":
        extra = man.get("extra") or {}
        stall = extra.get("stall")
        if not stall or "where" not in stall:
            return fail("watchdog bundle carries no stall record")
        if not extra.get("thread_stacks"):
            return fail("watchdog bundle names no thread-stack "
                        "attachment")
        threads = bundle.get("threads")
        if not threads or not threads.strip():
            return fail(f"thread-stack attachment "
                        f"{extra['thread_stacks']!r} is missing or "
                        f"empty")
        if "thread" not in threads.lower():
            return fail("thread-stack attachment holds no thread "
                        "frames")
    # trace structure: a dict with an event list; every event carries
    # ph/ts (pairing can be legitimately unbalanced when the trace
    # ring dropped events)
    trace = bundle["trace"]
    events = trace.get("traceEvents") if isinstance(trace, dict) \
        else trace
    if not isinstance(events, list):
        return fail("trace.json has no traceEvents list")
    for ev in events:
        if "ph" not in ev or "ts" not in ev:
            return fail(f"trace event missing ph/ts: {ev}")
    print(f"OK: {bundle['dir']}: {len(steps)} steps, "
          f"{len(request_events(steps))} requests, "
          f"{len(events)} trace events all reconcile")
    return 0


def diff_bundles(a, b) -> int:
    """Metrics + step-count delta between two bundles (taken
    a-then-b)."""
    print(f"steps: {a['manifest'].get('steps_recorded')} -> "
          f"{b['manifest'].get('steps_recorded')}")
    d = snapshot_diff(a["metrics"], b["metrics"])
    moved = {k: v for k, v in d.items()
             if v.get("delta") or v.get("count_delta")
             or v.get("reset") or v.get("type") == "gauge"}
    for key in sorted(moved):
        desc = moved[key]
        flag = " [RESET]" if desc.get("reset") else ""
        if desc["type"] == "counter":
            print(f"{key:<52} +{desc['delta']}{flag}")
        elif desc["type"] == "histogram":
            print(f"{key:<52} +{desc['count_delta']} samples "
                  f"(+{desc['sum_delta']:.6g}){flag}")
        else:
            print(f"{key:<52} = {desc['value']}{flag}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("bundle", help="postmortem bundle directory")
    ap.add_argument("--last-n-steps", type=int, default=None,
                    metavar="N",
                    help="render only the newest N step records "
                    "(default 10 when rendering)")
    ap.add_argument("--request", type=int, default=None, metavar="UID",
                    help="slice to the steps involving one request "
                    "and print its admit->finish path")
    ap.add_argument("--assert-complete", action="store_true",
                    help="gate mode: exit 1 unless every bundle file "
                    "parses and cross-reconciles")
    ap.add_argument("--diff", default=None, metavar="OTHER",
                    help="diff this bundle's metrics against OTHER "
                    "(taken bundle-then-OTHER)")
    args = ap.parse_args(argv)
    try:
        bundle = load_bundle(args.bundle)
    except BundleError as e:
        print(f"FAIL: unreadable bundle: {e}", file=sys.stderr)
        return 1
    if args.assert_complete:
        return assert_complete(bundle)
    if args.diff is not None:
        try:
            other = load_bundle(args.diff)
        except BundleError as e:
            print(f"FAIL: unreadable bundle: {e}", file=sys.stderr)
            return 1
        return diff_bundles(bundle, other)
    if args.last_n_steps is None:
        args.last_n_steps = 10
    return render(bundle, args)


if __name__ == "__main__":
    sys.exit(main())
