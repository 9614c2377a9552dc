"""What PR 26 added to the benchmark: the reader of the instructions'
metadata (``harness/trace_stats.py``), the device time by layer
(``readers/trace_scope.py``) on a trace recorded on the chip, the host-span
helpers (``harness/trace_host.py``) and the three serving metrics that wait
in ``benchmarks/pending/``."""
import json
from pathlib import Path

import pytest
from conftest import with_pending

from benchmarks.harness import loader, trace_host, trace_reduce, trace_stats
from benchmarks.readers import (trace_idle_unattributed, trace_program_share,
                                trace_scope)

HERE = Path(__file__).resolve().parent
TRAIN_METRICS = {"attn_ms_per_step": "attn", "mlp_ms_per_step": "mlp",
                 "head_ms_per_step": "head",
                 "optimizer_ms_per_step": "optimizer",
                 "other_ms_per_step": "other"}
SERVE_METRICS = ("prefill_busy_share_pct", "fair_queue_wait_mean_ms",
                 "idle_unattributed_pct.serve")

XSPACE = '''
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 100
    events { metadata_id: 9 offset_ps: 0 duration_ps: 90000 } }
  lines { name: "XLA Ops" timestamp_ns: 100
    events { metadata_id: 1 offset_ps: 1000 duration_ps: 5000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 7000 duration_ps: 2500 } }
  lines { name: "Steps" timestamp_ns: 100
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    display_name: "fusion.1"
    stats { metadata_id: 2 str_value: "jit(step)/gpt/h/3/attn/dot_general:" }
    stats { metadata_id: 3 ref_value: 2 }
    stats { metadata_id: 4 int64_value: -5 }
    stats { metadata_id: 5 double_value: 1.5 } } }
  event_metadata { key: 2 value { id: 2
    name: "%causal_flash_bwd.3 = bf16[8]{0} custom-call(bf16[8]{0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 9 value { id: 9 name: "jit_step(123)" } }
  stat_metadata { key: 1 value { id: 1 name: "evstat" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "refstat" } }
  stat_metadata { key: 4 value { id: 4 name: "neg" } }
  stat_metadata { key: 5 value { id: 5 name: "dbl" } }
}
planes { name: "/host:CPU" lines { name: "main"
  events { metadata_id: 1 offset_ps: 1 duration_ps: 2 } }
  event_metadata { key: 1 value { id: 1 name: "bench.x" } } }
'''


@pytest.fixture
def xplane(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


@pytest.fixture(scope="module")
def recorded():
    """Every 24th operation of one ``jit_step`` of ``gpt2m-pretrain`` and
    its 24 longest, each with the scope its instruction carries (my chip run, PR 26;
    ``tools/trace_scope_dump.py --record``)."""
    with open(HERE / "recorded_scope.json") as f:
        rec = json.load(f)
    return {"device": {int(k): [tuple(e) for e in v]
                       for k, v in rec["device"].items()},
            "modules": {int(k): [tuple(e) for e in v]
                        for k, v in rec["modules"].items()}}


def test_metadata_stats_are_read_from_the_wire(xplane):
    planes = trace_stats.load_planes(xplane)
    assert set(planes) == {0}                    # the host plane is left
    chip = planes[0]
    assert chip["ops"] == [(1, 101, 5.0), (2, 107, 2.5)]
    assert chip["modules"] == [(9, 100, 90.0)]
    name, display, stats = chip["meta"][1]
    assert name.startswith("%fusion.1 = ") and display == "fusion.1"
    assert stats == {"tf_op": "jit(step)/gpt/h/3/attn/dot_general:",
                     "refstat": "tf_op", "neg": -5, "dbl": 1.5}


def test_operations_carry_their_scope_and_agree_with_load_events(xplane):
    ops = trace_stats.load_ops(xplane)
    assert ops["device"][0] == [
        ("%fusion.1 bf16[8] fusion", 101, 5.0,
         "jit(step)/gpt/h/3/attn/dot_general:"),
        ("%causal_flash_bwd.3 bf16[8] custom-call:tpu_custom_call", 107,
         2.5, "")]
    assert ops["modules"][0] == [("jit_step", 100, 90.0)]
    plain = trace_reduce.load_events(xplane)     # jax's own reader
    assert [(n, s) for n, s, _, _ in ops["device"][0]] == \
        [(n, s) for n, s, _ in plain["device"][0]]


def test_a_kernel_is_found_by_name_without_a_scope(xplane):
    t = trace_scope.table(trace_stats.load_ops(xplane),
                          trace_scope.load_scopes("gpt2-train"), steps=1)
    assert t["groups"]["attn"] == {"fwd": pytest.approx(7.5e-6), "bwd": 0.0}
    assert t["kernels"] == {"causal_flash_bwd": {
        "ms": pytest.approx(2.5e-6), "calls": 1}}
    assert t["named"] == 2 and t["unscoped_ms"] == pytest.approx(2.5e-6)


def test_groups_partition_the_recorded_operations(recorded):
    scopes = trace_scope.load_scopes("gpt2-train")
    t = trace_scope.table(recorded, scopes, steps=1)
    ev = [e for e in recorded["device"][0]
          if not e[0].endswith(trace_scope.CONTAINERS)]
    assert len(ev) > 300
    # every operation lands in exactly one group: the groups' times add
    # up to the operations' own, and no group but the last is empty
    assert abs(t["total_ms"] - sum(d for _, _, d, _ in ev) / 1e6) < 1e-9
    assert all(v["fwd"] + v["bwd"] > 0 for v in t["groups"].values())
    assert list(t["groups"]) == [g for g, _ in scopes["groups"]]
    assert t["groups"]["optimizer"]["bwd"] == 0.0
    assert t["groups"]["mlp"]["bwd"] > t["groups"]["mlp"]["fwd"] > 0
    assert set(t["kernels"]) <= {"causal_flash_bwd", "causal_flash_fwd_row"}
    assert t["kernels"], "a kernel of its own name among the recorded"
    import re
    groups = [(g, re.compile(p)) for g, p in scopes["groups"]]
    first = {}
    for name, _, d, scope in ev:
        first[trace_scope.group_of(groups, scope, name)] = True
        assert sum(bool(rx.search(f"{scope} {name}"))
                   for _, rx in groups) >= 1
    assert set(first) == set(t["groups"])


def test_a_program_without_scopes_reads_nothing(recorded, monkeypatch,
                                                tmp_path):
    """The parent of PR 26 names no layer: the reader returns nothing for
    every metric and does not raise."""
    bare = {"device": {0: [(n.replace("causal_flash", "transpose_jvp"), s,
                            d, "") for n, s, d, _ in recorded["device"][0]]},
            "modules": recorded["modules"]}
    monkeypatch.setattr(trace_reduce, "newest_xplane", lambda d: "bare.pb")
    monkeypatch.setattr(trace_stats, "load_ops", lambda p: bare)
    out = {"trace": {"busy_s": 1.0}, "facts": {"traced_steps": 1}}
    for name, group in TRAIN_METRICS.items():
        spec = {"name": name, "params": {"scopes": "gpt2-train",
                                         "group": group}}
        assert trace_scope.read(spec, out, {}) is None
    assert trace_scope.read(spec, {"trace": None, "facts": {}}, {}) is None


def test_train_cell_rehearsal_prints_the_five_readings(recorded, capsys,
                                                       monkeypatch):
    """The cell's own metric files and the chip's recorded operations,
    through ``read`` as ``run.py`` calls it. On a CPU there is no device
    plane (a traced run is refused, test_rehearsal.py): the numbers here
    are the recording's, one step of it, not this machine's."""
    monkeypatch.setattr(trace_reduce, "newest_xplane",
                        lambda d: "recorded_scope")
    monkeypatch.setattr(trace_stats, "load_ops", lambda p: recorded)
    cell = loader.load_cell("gpt2m-pretrain")
    specs = {m["name"]: m for m in cell["per_layer"]}
    assert set(TRAIN_METRICS) <= set(specs)
    busy_ms = sum(d for n, _, d, _ in recorded["device"][0]
                  if not n.endswith(trace_scope.CONTAINERS)) / 1e6
    out = {"trace": {"busy_s": busy_ms / 1e3}, "facts": {"traced_steps": 1}}
    values = {n: loader.find("readers", specs[n]["reader"]).read(
        specs[n], out, {}) for n in TRAIN_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert abs(sum(values.values()) - busy_ms) < 0.01 * busy_ms
    assert {specs[n]["params"]["scopes"] for n in TRAIN_METRICS} == \
        {"gpt2-train"}
    printed = capsys.readouterr().out
    assert printed.count("device time by layer") == 1   # one table a run
    assert "kernel causal_flash_bwd" in printed


def test_serving_metric_files_load_through_with_pending(monkeypatch):
    monkeypatch.setattr(loader, "benchmark", with_pending)
    cell = loader.load_cell("mistral7b-chat")
    specs = {m["name"]: m for m in cell["per_layer"]}
    assert set(SERVE_METRICS) <= set(specs)
    e2e = {m["name"] for m in cell["end_to_end"]}
    for name in SERVE_METRICS:
        assert specs[name]["moves"] in e2e
        loader.find("readers", specs[name]["reader"])
    pending = json.loads((HERE.parent / "pending"
                          / "mistral7b-chat.tracing.json").read_text())
    assert [m["name"] for m in pending["per_layer"]] == list(SERVE_METRICS)
    assert pending["configs"] == pending["workloads"] == \
        pending["end_to_end"] == []


HOST = [("frontend.loop", 0, 100), ("frontend.feed", 2, 8),
        ("engine.step", 12, 80), ("engine.admit", 14, 20),
        ("engine.prefill_dispatch", 20, 10), ("engine.harvest", 50, 40),
        ("frontend.loop", 100, 50), ("frontend.idle_wait", 105, 40),
        ("bench.submit", 300, 10)]


def test_self_time_is_a_span_less_its_children():
    st = trace_host.self_times(HOST)
    assert st["engine.prefill_dispatch"] == [10e-9, 10e-9, 1]
    assert st["engine.admit"] == [10e-9, 20e-9, 1]
    assert st["engine.step"] == [20e-9, 80e-9, 1]
    assert st["frontend.loop"][1:] == [150e-9, 2]
    assert abs(st["frontend.loop"][0] - 22e-9) < 1e-15   # 12 + 10
    assert "bench.submit" not in st
    assert abs(sum(v[0] for v in st.values()) - 150e-9) < 1e-15


def test_gaps_are_named_by_the_innermost_span():
    device = [("a", 0, 10), ("b", 30, 10), ("c", 60, 20), ("d", 200, 10)]
    gaps = trace_host.gaps(device)
    assert gaps == [(120, 80), (20, 40), (20, 10)]
    assert trace_host.gaps(device, top=1) == [(120, 80)]
    named = trace_host.name_gaps(gaps, HOST)
    # [10,30) -> engine.prefill_dispatch; [40,60) -> engine.harvest;
    # [80,200): its middle, 140, lies in frontend.idle_wait
    assert named == {"frontend.idle_wait": 120e-9,
                     "engine.prefill_dispatch": 20e-9,
                     "engine.harvest": 20e-9}
    assert trace_host.name_gaps([(10, 400)], HOST) == {"unattributed": 10e-9}
    assert trace_host.union_ns([(0, 10), (5, 10), (30, 5)]) == 20


def _serve_events():
    return {"device": {0: [("a", 0, 10), ("b", 5, 10), ("c", 30, 10),
                           ("d", 200, 10)]},
            "modules": {0: [("jit_prefill", 0, 20),
                            ("jit_decode_chain", 28, 190)]},
            "host": list(HOST)}


def test_serving_trace_metrics_on_hand_made_events(monkeypatch, capsys):
    events = _serve_events()
    monkeypatch.setattr(trace_reduce, "newest_xplane", lambda d: "x.pb")
    monkeypatch.setattr(trace_host, "events_for", lambda p: events)
    out = {"trace": {"busy_s": 1.0}}
    share = trace_program_share.read(
        {"name": "prefill_busy_share_pct",
         "params": {"program": "^jit_prefill"}}, out, {})
    assert share == pytest.approx(100.0 * 15 / 35)
    # gaps [15,30) under engine.admit/prefill..., [40,200): frontend/bench
    # cover part of it; nothing is left without a span
    un = trace_idle_unattributed.read(
        {"name": "idle_unattributed_pct.serve", "params": {"top": 8}},
        out, {})
    assert un == 0.0
    events["host"] = [h for h in HOST if h[0].startswith("bench.")]
    un = trace_idle_unattributed.read(
        {"name": "idle_unattributed_pct.serve", "params": {"top": 8}},
        out, {})
    assert un == 100.0          # a program without spans: all unnamed
    printed = capsys.readouterr().out
    assert "gaps by innermost span" in printed
    assert "host span engine.step: self" in printed
    for reader in (trace_program_share, trace_idle_unattributed):
        assert reader.read({"name": "x", "params": {}},
                           {"trace": None}, {}) is None


def test_serving_rehearsal_reads_what_a_cpu_can(tiny_cells, capsys,
                                                monkeypatch):
    """The pending chat cell at tiny sizes on the CPU: the fair-queue wait
    is a registry delta and reads here; the two trace metrics need a device
    plane, which a CPU trace has not (``trace`` is None: nothing returned,
    nothing raised)."""
    from benchmarks.drivers import serve_open_loop

    box, real = {}, serve_open_loop.run
    monkeypatch.setattr(serve_open_loop, "run",
                        lambda ctx: box.setdefault("out", real(ctx)))
    rc = tiny_cells.main(["--workload", "mistral7b-chat", "--seed",
                          str(2**31 + 26), "--seconds", "3", "--trace", "0"])
    capsys.readouterr()
    assert rc == 0
    out = box["out"]
    specs = {m["name"]: m
             for m in loader.load_cell("mistral7b-chat")["per_layer"]}
    read = lambda n: loader.find("readers", specs[n]["reader"]).read(
        specs[n], out, {})
    wait = read("fair_queue_wait_mean_ms")
    assert wait is not None and wait >= 0.0
    after = out["registry"][1]["paddle_serving_fair_queue_wait_seconds"]
    before = out["registry"][0].get(
        "paddle_serving_fair_queue_wait_seconds", {"series": {}})
    count = lambda e: sum(s["count"] for s in e["series"].values())
    assert count(after) - count(before) > 0
    assert read("prefill_busy_share_pct") is None
    assert read("idle_unattributed_pct.serve") is None
