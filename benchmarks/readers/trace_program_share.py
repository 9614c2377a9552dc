"""Share of the device's busy time spent in the programs whose name matches
``program`` (``^jit_prefill``): the union of the intervals of the
operations that ran inside such a program over the union of all of them, so
an operation and the loop that holds it are not counted twice."""
import re

from ..harness import trace_host, trace_reduce
from ..harness.trace_window import TRACE_DIR


def share(events, pattern):
    rx = re.compile(pattern)
    inside = busy = 0
    for chip, dev in events["device"].items():
        at = trace_host.program_at(events.get("modules", {}).get(chip, []))
        inside += trace_host.union_ns(
            (s, d) for _, s, d in dev if rx.search(at(s) or ""))
        busy += trace_host.union_ns((s, d) for _, s, d in dev)
    return (inside, busy)


def read(spec, out, ctx):
    if not out.get("trace"):
        return None
    try:
        path = trace_reduce.newest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    inside, busy = share(trace_host.events_for(path),
                         spec["params"]["program"])
    if not busy:
        return None
    print(f"{spec['name']}: {inside / 1e9:.4f} s of {busy / 1e9:.4f} s busy "
          f"inside programs matching {spec['params']['program']!r}",
          flush=True)
    return 100.0 * inside / busy
