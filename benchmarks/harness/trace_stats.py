"""The device's executed operations WITH what the profiler knows of each.

``trace_reduce.load_events`` keeps an operation's name, start and duration;
jax's ``ProfileData`` hands out an event's own stats only, and on the chip
those are three clock values. What says where an operation came from (the
name stack of the jax operation it was compiled from: ``jit(step)/jvp(...)/
gpt/h/3/attn/...``; for a Pallas kernel its ``name=``) sits in the
``.xplane.pb`` one level up, on the event's METADATA (one record per HLO
instruction). This module reads that: the file's wire format directly, a few
message types of ``xplane.proto`` (XSpace.planes=1; XPlane.name=2 lines=3
event_metadata=4 stat_metadata=5; XLine.name=2 timestamp_ns=3 events=4;
XEvent.metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata.id=1 name=2
display_name=4 stats=5; XStat.metadata_id=1 double=2 uint64=3 int64=4 str=5
bytes=6 ref=7; XStatMetadata.id=1 name=2), skipping every line but the two
it wants, so nothing but the standard library is needed.

``load_ops`` returns, per chip, the operations as ``(name, start_ns, dur_ns,
scope)`` with ``name`` shortened as ``trace_reduce.short_op`` does and
``scope`` the value of the stat ``SCOPE_STAT`` on the instruction's metadata
("" where it has none: the compiler's own copies and waits), and the
programs (``XLA Modules``) they ran in.
"""
import struct

from .trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, short_module,
                           short_op)

# the metadata stat under which a chip's trace carries the jax name stack of
# an operation (found on the chip, PR 26; PERF.md section 3)
SCOPE_STAT = "tf_op"


def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one message: an int for a varint or fixed
    field, (start, end) offsets into ``buf`` for a length-delimited one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        no, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield no, v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span):
    """(stat metadata id, value, is_ref) of one XStat."""
    key, value, ref = 0, None, False
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = struct.unpack("<d", v)[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no in (5, 6):
            value = _text(buf, v)
        elif no == 7:
            value, ref = v, True
    return key, value, ref


def _map_entry(buf, span):
    """(key, value span) of one entry of a map<int64, message>."""
    key, value = 0, None
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _event_metadata(buf, span):
    name, display, stats = "", "", []
    for no, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 4:
            display = _text(buf, v)
        elif no == 5:
            stats.append(_stat(buf, v))
    return name, display, stats


def _line(buf, span):
    """(name, [(metadata id, start_ns, dur_ns)]) of one XLine; the events
    are read only of the two lines wanted."""
    name, t0_ns, events = "", 0, []
    for no, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            t0_ns = v
        elif no == 4:
            events.append(v)
    if name not in (OPS_LINE, MODULES_LINE):
        return name, []
    out = []
    for ev in events:
        meta = offset_ps = dur_ps = 0
        for no, v in _fields(buf, *ev):
            if no == 1:
                meta = v
            elif no == 2:
                offset_ps = v
            elif no == 3:
                dur_ps = v
        out.append((meta, t0_ns + offset_ps // 1000, dur_ps / 1000.0))
    return name, out


def load_planes(xplane_path):
    """{chip: {"ops": [(metadata id, start_ns, dur_ns)], "modules": [...],
    "meta": {id: (name, display name, {stat name: value})}}} of every
    device plane of the file."""
    with open(xplane_path, "rb") as f:
        buf = memoryview(f.read())
    chips = {}
    for no, plane in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for pno, v in _fields(buf, *plane):
            if pno == 2:
                name = _text(buf, v)
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                metas.append(v)
            elif pno == 5:
                key, value = _map_entry(buf, v)
                for sno, sv in _fields(buf, *value):
                    if sno == 2:
                        stat_names[key] = _text(buf, sv)
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        chip = {"ops": [], "modules": [], "meta": {}}
        for span in lines:
            lname, events = _line(buf, span)
            if lname == OPS_LINE:
                chip["ops"] = events
            elif lname == MODULES_LINE:
                chip["modules"] = events
        for span in metas:
            key, value = _map_entry(buf, span)
            mname, display, stats = _event_metadata(buf, value)
            chip["meta"][key] = (mname, display, {
                stat_names.get(k, str(k)):
                    (stat_names.get(v, str(v)) if ref else v)
                for k, v, ref in stats})
        chips[int(m.group(1))] = chip
    return chips


def load_ops(xplane_path):
    """{"device": {chip: [(name, start_ns, dur_ns, scope)]}, "modules":
    {chip: [(name, start_ns, dur_ns)]}}: as ``trace_reduce.load_events``
    gives them, each operation with the scope its instruction carries."""
    device, modules = {}, {}
    for chip, plane in load_planes(xplane_path).items():
        known = {k: (short_op(name), str(stats.get(SCOPE_STAT, "")))
                 for k, (name, _, stats) in plane["meta"].items()}
        ops = []
        for k, s, d in plane["ops"]:
            name, scope = known.get(k, (str(k), ""))
            ops.append((name, s, d, scope))
        device[chip] = ops
        modules[chip] = [
            (short_module(plane["meta"].get(k, (str(k),))[0]), s, d)
            for k, s, d in plane["modules"]]
    return {"device": device, "modules": modules}
