"""Share of the device's idle time that no host span of the program or the
benchmark covers: the traced window's ``top`` longest idle gaps
(``trace_reduce.reduce`` names each by the host event that covers most of
it), the time of those left ``unattributed`` over the time of all of them.
The host events read are the benchmark's ``bench.*`` and the program's own
``engine.*`` / ``frontend.*`` spans, which the tracer writes into the
profiler's trace. A program without such spans (a parent commit from before
them) leaves every gap that is not the benchmark's unattributed; a trace
with no idle gap returns nothing.

Once per run it prints what the number cannot say: the same gaps named by
the INNERMOST span that holds each gap's middle, and the engine thread's
self time by phase (a span less the spans it contains)."""
from ..harness import trace_host, trace_reduce
from ..harness.trace_window import TRACE_DIR


def read(spec, out, ctx):
    if not out.get("trace"):
        return None
    try:
        path = trace_reduce.newest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    top = int(spec["params"].get("top", 256))
    events = trace_host.events_for(path)
    red = trace_reduce.reduce(events, top=top)
    if not red or not red["idle_gaps"]:
        return None
    by_name = dict(red["idle_gaps"])
    total = sum(by_name.values())
    if total <= 0:
        return None
    idle = red["window_s"] - red["busy_s"]
    print(f"{spec['name']}: {idle:.4f} s idle of {red['window_s']:.4f} s; "
          f"the {top} longest gaps hold {total:.4f} s, "
          f"{by_name.get('unattributed', 0.0):.4f} s of it under no host "
          "span", flush=True)
    chip = min(events["device"])
    inner = trace_host.name_gaps(
        trace_host.gaps(events["device"][chip], top), events["host"])
    print(f"{spec['name']}: gaps by innermost span (s): "
          + ", ".join(f"{n} {t:.4f}" for n, t in inner.items()), flush=True)
    for name, (self_s, total_s, count) in sorted(
            trace_host.self_times(events["host"]).items(),
            key=lambda kv: -kv[1][0]):
        print(f"{spec['name']}: host span {name}: self {self_s:.4f} s of "
              f"{total_s:.4f} s in {count} spans", flush=True)
    return 100.0 * by_name.get("unattributed", 0.0) / total
