"""A kernel's share of its roofline: the least time the chip could take for
what the kernel had to do (the ``cost`` module the metric's file names,
under ``benchmarks/costs/``: from shapes and the driver's counts) over the summed device time of the operations whose
name matches ``pattern``. Nothing to read (no trace, no such operation, no
work counted) returns nothing."""
from ..harness import costs, loader, trace_reduce


def read(spec, out, ctx):
    tr = out.get("trace")
    if not tr:
        return None
    p = spec["params"]
    seconds = trace_reduce.kernel_seconds(tr, p["pattern"])
    cost = loader.find("costs", p["cost"]).cost(ctx["config"], out["facts"])
    if not seconds or not (cost["flops"] or cost["bytes"]):
        return None
    least, bound = costs.roofline_seconds(cost["flops"], cost["bytes"],
                                          ctx["peaks"])
    print(f"{spec['name']}: {seconds:.6f} s in operations matching "
          f"{p['pattern']!r}; least {least:.6f} s, bound by {bound}",
          flush=True)
    return 100.0 * least / seconds
