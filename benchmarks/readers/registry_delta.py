"""Window delta of one of the program's registry metrics, from the two
snapshots the driver took (``REGISTRY.snapshot()`` at the window's start and
end), summed over label series. ``stat``: ``mean`` of a histogram (delta of
sum over delta of count, times ``scale``) or ``total`` of a counter."""


def _totals(snapshot, name):
    entry = snapshot.get(name)
    if entry is None:
        return None
    if "series" in entry:
        return (sum(s["sum"] for s in entry["series"].values()),
                sum(s["count"] for s in entry["series"].values()))
    return (sum(entry["values"].values()), None)


def read(spec, out, ctx):
    reg = out.get("registry")
    if not reg:
        return None
    p = spec["params"]
    before = _totals(reg[0], p["metric"]) or (0.0, 0)
    after = _totals(reg[1], p["metric"])
    if after is None:
        return None
    scale = p.get("scale", 1.0)
    if p["stat"] == "total":
        return scale * (after[0] - before[0])
    count = after[1] - (before[1] or 0)
    if not count:
        return None
    return scale * (after[0] - before[0]) / count
