#!/usr/bin/env python
"""trace_tpu — export paddle_tpu trace snapshots as Chrome trace-event
JSON (loadable in Perfetto / ``chrome://tracing``).

Two input paths (ISSUE 18):

    # live scrape from a serving ApiServer started with --trace on
    python tools/trace_tpu.py --from-url http://127.0.0.1:8000 \
        --out trace.json

    # a flight-recorder JSONL postmortem (or a saved /debug/trace body)
    python tools/trace_tpu.py --from-file flight-*.jsonl --out trace.json

    # validate a produced file round-trips (the make trace-smoke gate)
    python tools/trace_tpu.py --check trace.json

Input records are the tracer's ring schema (one dict per span/instant;
see ``paddle_tpu/observability/tracing.py``): ``ts`` is wall-clock
seconds, ``dur`` seconds-or-None, ``proc``/``tid`` the process label and
thread id. Output is the Chrome trace-event JSON object format::

    {"traceEvents": [
        {"ph": "M", "name": "process_name", ...},         # metadata
        {"name": "engine.step", "cat": "engine", "ph": "X",
         "ts": <µs>, "dur": <µs>, "pid": 0, "tid": ...,
         "args": {"trace": ..., "id": ..., ...}}, ...]}

Durations convert to microseconds; timestamps rebase to the earliest
record so Perfetto's viewport opens on the data. Multiple inputs (a
router's main-process file plus each replica's) merge on the shared
wall clock — that merge is what renders a migrated stream as ONE
contiguous cross-replica trace.

This tool renders the tracer's own ring: requests across replicas on the
wall clock. To see the same spans AGAINST THE DEVICE, take a
``jax.profiler`` trace instead (``jax.profiler.start_trace(dir)`` around
the serving process, or ``paddle_tpu.profiler.Profiler``): every span
opened through ``Tracer.start`` / ``nested`` / ``tracing.span`` is also a
``jax.profiler.TraceAnnotation`` of the same name, whatever the tracer's
mode, so Perfetto / XProf show ``frontend.loop`` > ``engine.step`` >
``engine.harvest`` on the host's line beside the chip's ``XLA Ops`` line
on one clock, and each device operation carries its layer's
``jax.named_scope`` path (``.../gpt/h/3/attn/...``) and each Pallas
kernel its own name. ``benchmarks/readers/trace_scope.py`` and
``benchmarks/harness/trace_host.py`` reduce such a trace to numbers.

Pure stdlib; no paddle_tpu import (runs anywhere, even where jax is
broken).
"""
import argparse
import json
import sys
import urllib.request


def load_snapshot(path: str):
    """Records from a file: a flight-recorder JSONL (header line +
    one record per line), a bare JSONL of records, a saved
    /debug/trace JSON body, or an already-converted Chrome trace (its
    records pass through ``--check``)."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    text = text.strip()
    if not text:
        return []
    # whole-body JSON first (a saved /debug/trace scrape); a JSONL file
    # fails this parse and falls through to per-line decoding
    try:
        body = json.loads(text)
    except ValueError:
        body = None
    if isinstance(body, dict) and "records" in body:
        return list(body["records"])
    if isinstance(body, list):
        return body
    records = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        rec = json.loads(line)
        if rec.get("kind") == "flight":
            continue  # the postmortem header line
        records.append(rec)
    return records


def fetch_snapshot(url: str, timeout_s: float = 10.0):
    """Records from a live server: ``url`` may be the server root or
    the full /debug/trace path."""
    if not url.rstrip("/").endswith("/debug/trace"):
        url = url.rstrip("/") + "/debug/trace"
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        body = json.loads(resp.read().decode("utf-8"))
    if isinstance(body, dict) and "error" in body:
        raise SystemExit(f"server refused the scrape: {body['error']}")
    return list(body.get("records", []))


def to_chrome_trace(records):
    """Tracer ring records -> Chrome trace-event JSON object."""
    records = [r for r in records if isinstance(r, dict) and "ts" in r]
    if not records:
        return {"traceEvents": []}
    t0 = min(float(r["ts"]) for r in records)
    procs = {}  # proc label -> synthetic pid
    events = []
    for r in records:
        proc = str(r.get("proc", "main"))
        pid = procs.setdefault(proc, len(procs))
        args = dict(r.get("args") or {})
        args["trace"] = r.get("trace")
        args["id"] = r.get("id")
        if r.get("parent"):
            args["parent"] = r["parent"]
        ev = {"name": r.get("name", "?"), "cat": r.get("cat") or "misc",
              "ts": (float(r["ts"]) - t0) * 1e6,
              "pid": pid, "tid": r.get("tid", 0), "args": args}
        dur = r.get("dur")
        if dur is None:
            ev["ph"] = "i"
            ev["s"] = "t"  # instant scoped to its thread
        else:
            ev["ph"] = "X"
            ev["dur"] = float(dur) * 1e6
        events.append(ev)
    events.sort(key=lambda e: e["ts"])
    meta = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": proc}} for proc, pid in procs.items()]
    return {"traceEvents": meta + events}


def check_chrome_trace(path: str) -> int:
    """Validate a converted file: parseable, non-empty, every event
    carries the phase-appropriate fields. Returns an exit code."""
    with open(path, "r", encoding="utf-8") as f:
        body = json.load(f)
    events = body.get("traceEvents")
    if not isinstance(events, list) or not events:
        print(f"check failed: {path}: no traceEvents", file=sys.stderr)
        return 1
    real = 0
    for ev in events:
        ph = ev.get("ph")
        if ph not in ("X", "i", "M"):
            print(f"check failed: unexpected phase {ph!r} in {ev}",
                  file=sys.stderr)
            return 1
        if ph == "M":
            continue
        for k in ("name", "ts", "pid", "tid"):
            if k not in ev:
                print(f"check failed: event missing {k!r}: {ev}",
                      file=sys.stderr)
                return 1
        if ph == "X" and "dur" not in ev:
            print(f"check failed: X event missing dur: {ev}",
                  file=sys.stderr)
            return 1
        real += 1
    if not real:
        print(f"check failed: {path}: metadata only, no span/instant "
              "events", file=sys.stderr)
        return 1
    print(f"ok: {path}: {real} events, "
          f"{len(events) - real} metadata records")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="export paddle_tpu traces as Chrome trace-event "
                    "JSON (Perfetto / chrome://tracing)")
    ap.add_argument("--from-url", action="append", default=[],
                    metavar="URL",
                    help="scrape a live ApiServer's /debug/trace "
                         "(repeatable; snapshots merge on wall clock)")
    ap.add_argument("--from-file", action="append", default=[],
                    metavar="PATH",
                    help="read a flight-recorder JSONL or saved "
                         "/debug/trace body (repeatable)")
    ap.add_argument("--out", default="trace.json",
                    help="output path (default trace.json)")
    ap.add_argument("--check", metavar="PATH",
                    help="validate an already-converted Chrome trace "
                         "file and exit")
    args = ap.parse_args(argv)
    if args.check:
        return check_chrome_trace(args.check)
    if not args.from_url and not args.from_file:
        ap.error("need --from-url or --from-file (or --check)")
    records = []
    for url in args.from_url:
        records.extend(fetch_snapshot(url))
    for path in args.from_file:
        records.extend(load_snapshot(path))
    trace = to_chrome_trace(records)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(trace, f)
    n = sum(1 for e in trace["traceEvents"] if e["ph"] != "M")
    print(f"wrote {args.out}: {n} events from {len(records)} records")
    return 0


if __name__ == "__main__":
    sys.exit(main())
