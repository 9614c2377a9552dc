"""The traffic generator: reproducible from a seed, the mix exact."""
import json

import numpy as np
from conftest import ROOT

from benchmarks.harness import traffic


def _mix():
    with open(ROOT / "benchmarks" / "traffic"
              / "chat-open-0.8knee.json") as f:
        return json.load(f)


def test_same_seed_same_schedule_and_ids():
    mix = _mix()
    a = traffic.schedule(mix, 40, seed=5)
    assert a == traffic.schedule(mix, 40, seed=5)
    assert (traffic.prompt_ids(5, 0, 3, 50, 32768)
            == traffic.prompt_ids(5, 0, 3, 50, 32768)).all()


def test_another_seed_other_ids_and_another_stream_other_order():
    mix = _mix()
    assert (traffic.prompt_ids(5, 0, 3, 50, 32768)
            != traffic.prompt_ids(6, 0, 3, 50, 32768)).any()
    assert traffic.schedule(mix, 40, 5, stream=0) \
        != traffic.schedule(mix, 40, 5, stream=1)
    # another seed, the same sizes and arrivals in another order
    a, b = traffic.schedule(mix, 10_000, 5), traffic.schedule(mix, 10_000, 6)
    assert a[:traffic.BLOCK] != b[:traffic.BLOCK]
    for col in (1, 2):
        assert sorted(r[col] for r in a[:traffic.BLOCK]) \
            == sorted(r[col] for r in b[:traffic.BLOCK])


def test_a_block_carries_the_mix_exactly():
    mix = _mix()
    long = traffic.schedule(mix, 10_000, seed=1)[:traffic.BLOCK]
    dues = np.array([d for d, _, _ in long])
    gaps = np.diff(np.concatenate([[0.0], dues]))
    assert abs(gaps.mean() - 1 / mix["rate_per_s"]) < 1e-9
    plen = sorted(p for _, p, _ in long)
    assert plen == sorted(traffic.length_quantiles(mix["prompt_len"],
                                                   traffic.BLOCK))
    assert mix["prompt_len"]["min"] <= plen[0]
    assert plen[-1] <= mix["prompt_len"]["max"]
    assert abs(np.median(plen) - mix["prompt_len"]["median"]) < 16
    olen = [o for _, _, o in long]
    assert min(olen) >= mix["output_len"]["min"]
    assert max(olen) <= mix["output_len"]["max"]


def test_seed_past_32_bits():
    assert len(traffic.prompt_ids(2**31 + 12345, 0, 0, 9, 100)) == 9


def test_nearest_rank():
    xs = list(range(1, 101))
    assert traffic.nearest_rank(xs, 90) == 90
    assert traffic.nearest_rank(xs, 50) == 50
    assert traffic.nearest_rank([3.0], 90) == 3.0
