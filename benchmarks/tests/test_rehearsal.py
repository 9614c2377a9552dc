"""A tiny-configuration rehearsal of both drivers through ``run.main``: the
last line's keys, as the contract of BENCHMARK.json has them."""
import json

import pytest

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def last_line(capsys):
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    return line, out.err


@pytest.mark.parametrize("workload,metrics", [
    ("gpt2m-pretrain", {"train_tokens_per_s", "setup_s"}),
    ("mistral7b-chat", {"ttft_p90_ms", "latency_per_token_p90_ms",
                        "out_tokens_per_s", "setup_s"})])
def test_untraced_last_line(tiny_cells, capsys, workload, metrics):
    rc = tiny_cells.main(["--workload", workload, "--seed",
                          str(2**31 + 11), "--seconds", "3", "--trace", "0"])
    line, err = last_line(capsys)
    assert rc == 0 and set(line) == KEYS
    assert list(line)[-1] == "checks"           # the comparison comes last
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    # each number compared stands beside its limit at the end of stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_traced_run_without_a_device_trace_is_refused(tiny_cells, capsys):
    """On the CPU the profiler records no device plane: a traced run has
    nothing to report and must exit non-zero, printing no result."""
    with pytest.raises(SystemExit) as e:
        tiny_cells.main(["--workload", "gpt2m-pretrain", "--seed", "3",
                         "--seconds", "2", "--trace", "1"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_no_chip_no_result(capsys):
    """The harness's own look for a chip: on the CPU it exits non-zero."""
    import benchmarks.run as run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "gpt2m-pretrain", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out
