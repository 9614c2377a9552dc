"""From a profiler trace to numbers: one reducer for every trace metric.

``load_events`` reads an ``.xplane.pb`` (jax alone reads it) into plain
tuples; ``reduce`` works on those, so the tests check it on a small recorded
list. Times are nanoseconds on the trace's own clock.

A device plane is named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed operation (a Pallas kernel is one such event). The host's
lines hold what the host threads did, the benchmark's own annotations
(``bench.*``) among them.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_LAYOUT = re.compile(r"\{[^}]*\}")
_RESULT_OP = re.compile(r"^(\([^)]*\)|\S+)\s+([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op(text):
    """An operation's event name is its whole HLO instruction; keep the
    instruction's name, its result type without layouts, its opcode and, for
    a custom call, the target (``tpu_custom_call`` is a Pallas kernel):
    ``%fusion.25 bf16[50304,1024] fusion``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:96]
    rest = _LAYOUT.sub("", rest)
    m = _RESULT_OP.match(rest)
    if not m:
        return head[:96]
    short = f"{head} {m.group(1)[:48]} {m.group(2)}"
    t = _TARGET.search(rest)
    return short + (f":{t.group(1)}" if t else "")


def short_module(text):
    """``jit_step(15525793419411081117)`` -> ``jit_step``."""
    return text.split("(", 1)[0]


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(xplane_path, host_prefix="bench."):
    """{"device": {chip: [(name, start_ns, dur_ns)]}, "modules": {chip:
    [...]}, "host": [...]}: the device's executed operations per chip
    (names shortened by ``short_op``), the programs they ran in, and the
    host events whose name starts with ``host_prefix``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip, short = int(m.group(1)), {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[chip] = [
                        (short.get(e.name) or short.setdefault(
                            e.name, short_op(e.name)),
                         int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[chip] = [
                        (short_module(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"device": device, "modules": modules, "host": host}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(events, window):
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def _in_modules(ev, modules):
    """Prefix each operation's name with the program it ran in
    (``jit_step/%fusion.25 ...``): the module whose interval holds the
    operation's start, or none."""
    import bisect

    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, d in ev:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            name = f"{mods[i][0]}/{name}"
        out.append((name, s, d))
    return out


def reduce(events, window=None, top=10):
    """Busy time, time by operation name, and the longest idle gaps.

    ``window`` (start_ns, end_ns) clips everything; without it the window
    runs from the first device event's start to the last one's end. Busy is
    the union of the intervals in which an operation ran, averaged over the
    chips; the gaps are those of the chip with the most idle time, each
    named by the host event (``bench.*``) that covers most of it."""
    chips = events["device"]
    if not chips or not any(chips.values()):
        return None
    if window is None:
        window = (min(s for ev in chips.values() for _, s, _ in ev),
                  max(s + d for ev in chips.values() for _, s, d in ev))
    window_ns = window[1] - window[0]
    busy_ns, by_name, worst = [], {}, None
    for chip, ev in sorted(chips.items()):
        ev = _in_modules(_clip(ev, window),
                         events.get("modules", {}).get(chip, []))
        merged = _union([(s, s + d) for _, s, d in ev])
        busy = sum(e - s for s, e in merged)
        busy_ns.append(busy)
        for name, _, d in ev:
            if name.endswith((" while", " conditional", " call")):
                continue  # a container: its body's operations are counted
            by_name[name] = by_name.get(name, 0) + d / len(chips)
        if worst is None or busy < worst[0]:
            worst = (busy, merged)
    edges = [window[0]] + [t for s, e in worst[1] for t in (s, e)] \
        + [window[1]]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:top]
    host = _clip(events.get("host", []), window)
    named = {}
    for dur, start in gaps:
        best, cover = "unattributed", 0
        for name, s, d in host:
            c = min(s + d, start + dur) - max(s, start)
            if c > cover:
                best, cover = name, c
        named[best] = named.get(best, 0) + dur
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "by_name_s": {n: t / 1e9 for n, t in ops},
        "device_ops": [[n, t / 1e9] for n, t in ops[:top]],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(named.items(), key=lambda kv: -kv[1])[:top]],
    }


def kernel_seconds(reduced, pattern):
    """Summed device time of the operations whose name matches."""
    rx = re.compile(pattern)
    hits = [t for n, t in reduced["by_name_s"].items() if rx.search(n)]
    return sum(hits) if hits else None
