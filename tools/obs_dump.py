"""Pretty-print observability artifacts: registry snapshots and traces.

Subcommands over the two export formats of
``apex_tpu.observability`` (``docs/observability.md``) and over a
server's ``stats()``:

``stats PATH``
    PATH is ``json.dump(server.stats(), f)``.  Prints what the pool
    keeps a token and layer (``memory.cache_kind``, its bytes as
    stored, the pool's bytes) and, for a family with expert layers, the
    ``experts`` block: rows routed in all, and for each expert layer
    its largest-over-mean ratio and its busiest and idlest experts
    (``docs/observability.md``, "Expert load").

``metrics PATH``
    PATH is either a ``MetricsRegistry.emit_jsonl`` scrape file (each
    line ``{"ts": ..., "metrics": {...}}`` — the LAST line is shown,
    or every line with ``--all``) or a bare ``snapshot()`` JSON dict.
    Prints one aligned row per series: counters as their value,
    gauges as value/peak/avg, histograms as count + p50/p90/p99/max
    in milliseconds-if-seconds-suffixed (``*_s`` series) else raw.

``trace PATH [PATH ...] [--require NAME ...] [--merge OUT]``
    Each PATH is a Chrome trace-event JSON
    (``SpanTracer.export_chrome`` / ``APEX_TPU_TRACE``).  Prints a
    per-span-name summary (count, total/mean/max wall) built by
    matching B/E pairs per thread, and an instant-event count table.
    With MULTIPLE paths (one per fleet replica), events are merged
    with each file's thread ids renamespaced to a dense map keyed by
    ``(file, pid, tid)`` — per-replica tracers all stamp the same
    OS thread ids from one process, so a naive concat interleaves
    different replicas' spans onto one Perfetto track and B/E pairing
    breaks; the remap keeps every replica's threads on distinct
    tracks, labeled ``replica{i}/tid{old}`` via ``thread_name``
    metadata events.  ``--merge OUT`` additionally writes the merged,
    renamespaced trace to OUT (Perfetto-loadable).  A single PATH is
    summarized as-is — no remap, byte-identical output to before.  When the tracer's ring buffer
    dropped events the summary is a truncated window, so a LOUD
    warning goes to stderr — a silently shortened trace reads as "the
    server did less", which is worse than no trace.  Each
    ``--require NAME`` asserts a span or instant of that name exists —
    exit 1 otherwise — which is how the build matrix checks a serve
    smoke actually traced its scheduler phases
    (``tests/build_matrix/run.sh``).  ``NAME`` may carry a label
    filter, ``name{key=value,...}``: the requirement then only matches
    events whose ``args`` carry every listed key with that exact
    (stringified) value — e.g. ``--require 'request_finish{reason=eos}'``.

Exit-code contract (the build matrix gates on ``--require``;
``tests/L0/test_tool_gates.py`` pins it): every assertion-style
failure — a missing/unreadable/malformed artifact, a ``--require``
name absent from the trace — exits 1 with a ``FAIL: ...`` line,
never a traceback.

Usage:
    python tools/obs_dump.py stats stats.json
    python tools/obs_dump.py metrics scrape.jsonl
    python tools/obs_dump.py trace trace.json --require admit --require decode
    python tools/obs_dump.py trace trace.json --require 'engine_oom{site=decode}'
    python tools/obs_dump.py trace rep0.json rep1.json rep2.json --merge fleet.json
"""

import argparse
import json
import sys


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _series_row(key: str, desc: dict) -> str:
    kind = desc.get("type", "?")
    if kind == "counter":
        detail = str(desc.get("value", 0))
    elif kind == "gauge":
        detail = (f"val={_fmt(desc.get('value', 0.0))} "
                  f"peak={_fmt(desc.get('peak', 0.0))} "
                  f"avg={_fmt(desc.get('avg', 0.0))}")
    elif kind == "histogram":
        if not desc.get("count"):
            detail = "count=0"
        else:
            scale, unit = ((1e3, "ms") if key.split("{")[0]
                           .endswith("_s") else (1, ""))
            detail = (f"count={desc['count']} "
                      f"p50={_fmt(desc['p50'] * scale)}{unit} "
                      f"p90={_fmt(desc['p90'] * scale)}{unit} "
                      f"p99={_fmt(desc['p99'] * scale)}{unit} "
                      f"max={_fmt(desc['max'] * scale)}{unit}")
    else:
        detail = json.dumps(desc)
    return f"{key:<44} {kind:<9} {detail}"


def dump_metrics(args) -> int:
    try:
        with open(args.path) as f:
            text = f.read()
    except OSError as e:
        print(f"FAIL: cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    records = []
    for i, line in enumerate(text.splitlines(), start=1):
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except ValueError as e:
            print(f"FAIL: {args.path}:{i} is not JSON: {e}",
                  file=sys.stderr)
            return 1
    if not records:
        print(f"{args.path}: empty", file=sys.stderr)
        return 1
    if not args.all:
        records = records[-1:]
    for rec in records:
        metrics = rec.get("metrics", rec)   # scrape line or bare snapshot
        if "ts" in rec:
            print(f"-- snapshot at ts={rec['ts']} "
                  f"({len(metrics)} series)")
        for key in sorted(metrics):
            print(_series_row(key, metrics[key]))
    return 0


def summarize_trace(events):
    """(span_stats, instant_counts, errors): span_stats maps name ->
    dict(count, total_us, max_us) from per-(pid, tid) B/E matching;
    unmatched or crossed pairs land in errors."""
    spans = {}
    instants = {}
    stacks = {}
    errors = []
    for ev in events:
        ph = ev.get("ph")
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks.setdefault(key, []).append(ev)
        elif ph == "E":
            st = stacks.get(key)
            if not st:
                errors.append(f"E without B on tid {key}")
                continue
            b = st.pop()
            name = b.get("name", "?")
            dur = ev["ts"] - b["ts"]
            s = spans.setdefault(name,
                                 {"count": 0, "total_us": 0.0,
                                  "max_us": 0.0})
            s["count"] += 1
            s["total_us"] += dur
            s["max_us"] = max(s["max_us"], dur)
        elif ph == "i":
            name = ev.get("name", "?")
            instants[name] = instants.get(name, 0) + 1
    for key, st in stacks.items():
        for b in st:
            errors.append(
                f"unclosed span {b.get('name')!r} on tid {key}")
    return spans, instants, errors


def parse_require(spec: str):
    """``name`` or ``name{key=value,...}`` -> (name, {key: value});
    raises ValueError on malformed filters."""
    if "{" not in spec:
        return spec, {}
    if not spec.endswith("}"):
        raise ValueError(f"malformed --require filter: {spec!r}")
    name, inner = spec[:-1].split("{", 1)
    labels = {}
    for part in inner.split(","):
        if "=" not in part:
            raise ValueError(
                f"--require filter needs key=value pairs: {spec!r}")
        k, v = part.split("=", 1)
        labels[k.strip()] = v.strip().strip('"')
    return name, labels


def require_matches(events, name: str, labels: dict) -> bool:
    """Whether any B/i event named ``name`` carries every filter label
    with that stringified value in its ``args``."""
    for ev in events:
        if ev.get("ph") not in ("B", "i") or ev.get("name") != name:
            continue
        args = ev.get("args", {})
        if all(str(args.get(k)) == v for k, v in labels.items()):
            return True
    return False


def merge_traces(loaded):
    """Merge ``(path, events)`` files into one event list with thread
    ids renamespaced densely by ``(file, pid, tid)`` — the fleet view.
    Per-replica tracers run in ONE process, so their raw traces carry
    the SAME OS thread ids; concatenating them would interleave
    different replicas' B/E spans on a single Perfetto track (pairing
    garbage).  Each new track gets a ``thread_name`` metadata event
    naming its origin, ``replica{i}/tid{old}``."""
    tids = {}
    merged = []
    for i, (path, events) in enumerate(loaded):
        for ev in events:
            key = (i, ev.get("pid"), ev.get("tid"))
            tid = tids.get(key)
            if tid is None:
                tid = tids[key] = len(tids)
                merged.append(
                    {"ph": "M", "name": "thread_name", "ts": 0,
                     "pid": ev.get("pid", 0), "tid": tid,
                     "args": {"name": f"replica{i}/tid{key[2]}"}})
            ev = dict(ev)
            ev["tid"] = tid
            merged.append(ev)
    return merged


def dump_trace(args) -> int:
    loaded = []
    dropped = 0
    for path in args.path:
        try:
            with open(path) as f:
                data = json.load(f)
        except OSError as e:
            print(f"FAIL: cannot read {path}: {e}", file=sys.stderr)
            return 1
        except ValueError as e:
            print(f"FAIL: {path} is not a JSON trace: {e}",
                  file=sys.stderr)
            return 1
        events = data["traceEvents"] if isinstance(data, dict) else data
        if not isinstance(events, list):
            print(f"FAIL: {path} carries no traceEvents list",
                  file=sys.stderr)
            return 1
        if isinstance(data, dict):
            dropped += data.get("otherData", {}).get(
                "dropped_events", 0)
        loaded.append((path, events))
    if len(loaded) == 1:
        # one file: no remap, output identical to the pre-merge tool
        label, events = loaded[0]
    else:
        label = f"{len(loaded)} traces merged"
        events = merge_traces(loaded)
    if args.merge is not None:
        try:
            with open(args.merge, "w") as f:
                json.dump({"traceEvents": events,
                           "otherData": {"dropped_events": dropped}},
                          f)
        except OSError as e:
            print(f"FAIL: cannot write {args.merge}: {e}",
                  file=sys.stderr)
            return 1
        print(f"merged trace -> {args.merge}")
    spans, instants, errors = summarize_trace(events)
    print(f"{label}: {len(events)} events, {len(spans)} span "
          f"names, {sum(instants.values())} instants"
          + (f", {dropped} dropped by the ring buffer" if dropped
             else ""))
    if dropped:
        print(f"WARNING: {dropped} events were DROPPED by the tracer "
              f"ring buffer — this trace is a truncated window, not "
              f"the full run (raise SpanTracer capacity, or treat "
              f"span counts as lower bounds)", file=sys.stderr)
    if spans:
        print(f"\n{'span':<20} {'count':>7} {'total ms':>10} "
              f"{'mean ms':>9} {'max ms':>9}")
        for name in sorted(spans, key=lambda n: -spans[n]["total_us"]):
            s = spans[name]
            print(f"{name:<20} {s['count']:>7} "
                  f"{s['total_us'] / 1e3:>10.3f} "
                  f"{s['total_us'] / s['count'] / 1e3:>9.3f} "
                  f"{s['max_us'] / 1e3:>9.3f}")
    if instants:
        print(f"\n{'instant':<20} {'count':>7}")
        for name in sorted(instants, key=lambda n: -instants[n]):
            print(f"{name:<20} {instants[name]:>7}")
    rc = 0
    for err in errors:
        print(f"WARN: {err}", file=sys.stderr)
    for spec in args.require or ():
        try:
            name, labels = parse_require(spec)
        except ValueError as e:
            print(f"FAIL: {e}", file=sys.stderr)
            rc = 1
            continue
        if labels:
            if not require_matches(events, name, labels):
                print(f"FAIL: no span/instant matches {spec!r}",
                      file=sys.stderr)
                rc = 1
        elif name not in spans and name not in instants:
            print(f"FAIL: required span/instant {name!r} not in trace",
                  file=sys.stderr)
            rc = 1
    return rc


def dump_stats(args) -> int:
    try:
        with open(args.path) as f:
            st = json.load(f)
        mem = st["memory"]
    except (OSError, ValueError, KeyError, TypeError) as e:
        print(f"FAIL: {args.path} is no stats() dump: {e!r}",
              file=sys.stderr)
        return 1
    print(f"cache: kind {mem.get('cache_kind', '?')}, "
          f"{mem.get('row_bytes_per_token_layer', '?')} B a token and "
          f"layer, pool {mem['pool_bytes']} B "
          f"({mem['pool_bytes_per_device']} B a device), "
          f"{mem['bytes_per_block']} B a block, dtype "
          f"{mem['cache_dtype']}")
    ex = st.get("experts") or {"enabled": False}
    if not ex.get("enabled"):
        print("experts: none (this family has no expert layers)")
        return 0
    print(f"experts: {ex['layers']} layers x {ex['experts_held']} held, "
          f"{ex['rows_routed']} rows routed")
    for i, (routed, ratio) in enumerate(zip(ex["routed"],
                                            ex["max_over_mean"])):
        order = sorted(range(len(routed)), key=routed.__getitem__)
        print(f"  layer {i}: max/mean {_fmt(ratio)}  busiest "
              + " ".join(f"{e}:{routed[e]}" for e in order[:-4:-1])
              + "  idlest "
              + " ".join(f"{e}:{routed[e]}" for e in order[:3]))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sp = sub.add_parser("stats", help="the cache's kind and bytes and "
                        "the expert load of a stats() JSON dump")
    sp.add_argument("path")
    sp.set_defaults(fn=dump_stats)
    mp = sub.add_parser("metrics",
                        help="pretty-print a registry snapshot / "
                        "JSON-lines scrape")
    mp.add_argument("path")
    mp.add_argument("--all", action="store_true",
                    help="print every scrape line, not just the last")
    mp.set_defaults(fn=dump_metrics)
    tp = sub.add_parser("trace",
                        help="summarize Chrome trace-event JSON "
                        "file(s); several (one per replica) are "
                        "merged with thread ids renamespaced per "
                        "file")
    tp.add_argument("path", nargs="+")
    tp.add_argument("--require", action="append", metavar="NAME",
                    help="exit 1 unless a span/instant NAME exists "
                    "(repeatable); NAME{key=value,...} additionally "
                    "matches event args")
    tp.add_argument("--merge", default=None, metavar="OUT",
                    help="write the merged, tid-renamespaced trace "
                    "to OUT (Perfetto-loadable)")
    tp.set_defaults(fn=dump_trace)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
