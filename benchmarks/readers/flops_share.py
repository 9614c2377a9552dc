"""The whole step's share of the chips' peak: the operations the window's
work needed (the ``cost`` module the metric's file names, under
``benchmarks/costs/``) over window x chips x peak."""
from ..harness import loader


def read(spec, out, ctx):
    facts = out["facts"]
    flops = loader.find("costs", spec["params"]["cost"]).cost(
        ctx["config"], facts)["flops"]
    if not flops or facts["window_s"] <= 0:
        return None
    return 100.0 * flops / (facts["window_s"] * ctx["chips"]
                            * ctx["peaks"]["flops_per_s"])
