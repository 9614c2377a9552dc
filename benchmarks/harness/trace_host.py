"""The program's own host spans in a profiler trace, beside the device.

The tracer (``paddle_tpu/observability/tracing.py``) enters a
``jax.profiler.TraceAnnotation`` for every span, so a trace holds the engine
thread's phases (``frontend.loop`` > ``engine.step`` > ``engine.harvest``
...) as host events on the device's clock. They carry no parent id there;
spans opened in ``with`` blocks on one thread nest by containment, which is
what ``self_times`` and ``innermost`` go by. Intervals are (start_ns,
dur_ns) as ``trace_reduce.load_events`` gives them.
"""
import bisect

from . import trace_reduce

PROGRAM_SPANS = ("bench.", "engine.", "frontend.")
_events = {}


def events_for(path, host_prefix=PROGRAM_SPANS):
    """``trace_reduce.load_events`` of ``path``, read once per process."""
    key = (path, host_prefix)
    if key not in _events:
        _events.clear()
        _events[key] = trace_reduce.load_events(path, host_prefix=host_prefix)
    return _events[key]


def program_at(modules):
    """start_ns -> the program (``XLA Modules`` event) whose interval holds
    it, or None."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]

    def at(start):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < mods[i][1] + mods[i][2]:
            return mods[i][0]
        return None
    return at


def union_ns(intervals):
    """Total length of the union of (start, duration) intervals."""
    total, end = 0, None
    for s, d in sorted(intervals):
        if end is None or s > end:
            total, end = total + d, s + d
        elif s + d > end:
            total, end = total + s + d - end, s + d
    return total


def gaps(device, top=None):
    """[(dur_ns, start_ns)] of the idle gaps between the first and the last
    operation of one chip's events, longest first."""
    edges = sorted((s, s + d) for _, s, d in device)
    out, end = [], None
    for s, e in edges:
        if end is not None and s > end:
            out.append((s - end, end))
        end = e if end is None else max(end, e)
    out.sort(reverse=True)
    return out[:top] if top else out


def self_times(host, prefixes=("engine.", "frontend.")):
    """{name: [self_s, total_s, count]} of the spans whose name starts with
    one of ``prefixes``: a span's self time is its duration less the part
    its children (the spans it contains) cover."""
    spans = sorted(((s, -d, n) for n, s, d in host
                    if n.startswith(tuple(prefixes))))
    out, stack = {}, []   # stack of [end, name, child_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, child = stack.pop()
            row = out.setdefault(name, [0.0, 0.0, 0])
            row[0] += (end - start - child) / 1e9
            row[1] += (end - start) / 1e9
            row[2] += 1
            if stack:
                stack[-1][3] += end - start
    for s, neg_d, n in spans:
        close(s)
        stack.append([s - neg_d, n, s, 0])
    close(float("inf"))
    return out


def innermost(host, t):
    """The name of the shortest span that holds time ``t``, or None."""
    best = None
    for n, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else None


def name_gaps(gap_list, host):
    """{name: seconds}: each gap under the innermost host span that holds
    its middle, ``unattributed`` where none does."""
    named = {}
    for dur, start in gap_list:
        name = innermost(host, start + dur // 2) or "unattributed"
        named[name] = named.get(name, 0.0) + dur / 1e9
    return dict(sorted(named.items(), key=lambda kv: -kv[1]))
