"""The trace reducer on a hand-made trace and on a small recorded one."""
import json
from pathlib import Path

from benchmarks.harness import trace_reduce as tr

HERE = Path(__file__).resolve().parent


def test_hand_made():
    events = {
        "device": {0: [("a", 0, 10), ("b", 5, 15), ("a", 30, 10)]},
        "modules": {0: [("jit_step", 0, 25), ("jit_other", 28, 20)]},
        "host": [("bench.wait", 18, 10), ("bench.feed", 29, 1)],
    }
    red = tr.reduce(events)
    assert red["window_s"] == 40e-9
    assert red["busy_s"] == 30e-9                 # [0,20) and [30,40)
    assert red["by_name_s"] == {"jit_step/a": 10e-9, "jit_step/b": 15e-9,
                                "jit_other/a": 10e-9}
    # one gap, [20,30): bench.wait covers 8 ns of it, bench.feed 1 ns
    assert red["idle_gaps"] == [["bench.wait", 10e-9]]
    assert tr.kernel_seconds(red, "^jit_step/") == 25e-9
    assert tr.kernel_seconds(red, "nothing") is None
    # a window clips events and adds the idle edges
    red = tr.reduce(events, window=(5, 50))
    assert red["busy_s"] == 25e-9 and red["window_s"] == 45e-9
    assert tr.reduce({"device": {}, "host": []}) is None


def test_short_names():
    text = ('%transpose_jvp___.45 = bf16[12,3,8,1024,128]{4,3,2,1,0:T(8,128)'
            '(2,1)S(1)} custom-call(bf16[12,24,1024,128]{3,2,1,0} %fusion.2),'
            ' custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.short_op(text) == ("%transpose_jvp___.45 "
                                 "bf16[12,3,8,1024,128] "
                                 "custom-call:tpu_custom_call")
    tup = ("%fusion.1 = (bf16[8]{0:T(8)(2,1)}, f32[2,4]{1,0}) fusion(f32[4]"
           "{0} %p), kind=kLoop")
    assert tr.short_op(tup) == "%fusion.1 (bf16[8], f32[2,4]) fusion"
    assert tr.short_module("jit_step(155257934)") == "jit_step"


def test_recorded_trace():
    """A few hundred device events recorded on the chip (a traced run of
    mistral7b-chat, tools/trace_dump.py --record): the reducer's busy time
    against a plain sweep over the nanoseconds' end points."""
    with open(HERE / "recorded_trace.json") as f:
        rec = json.load(f)
    events = {"device": {int(k): [tuple(e) for e in v]
                         for k, v in rec["device"].items()},
              "modules": {int(k): [tuple(e) for e in v]
                          for k, v in rec.get("modules", {}).items()},
              "host": [tuple(e) for e in rec["host"]]}
    red = tr.reduce(events)
    ev = events["device"][min(events["device"])]
    lo = min(s for _, s, _ in ev)
    hi = max(s + d for _, s, d in ev)
    assert abs(red["window_s"] - (hi - lo) / 1e9) < 1e-12
    # plain sweep: +1 at a start, -1 at an end, busy while the count > 0
    points = sorted([(s, 1) for _, s, _ in ev]
                    + [(s + d, -1) for _, s, d in ev],
                    key=lambda p: (p[0], -p[1]))  # a start before an end
    busy, depth, since = 0, 0, None
    for t, step in points:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert abs(red["busy_s"] - busy / 1e9) < 1e-12
    assert 0 < red["busy_s"] <= red["window_s"]
    containers = (" while", " conditional", " call")
    assert abs(sum(red["by_name_s"].values())
               - sum(d for n, _, d in ev
                     if not n.endswith(containers)) / 1e9) < 1e-9
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
