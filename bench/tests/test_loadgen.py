"""The traffic generator: same seed, same arrivals; every seed the same
work; the stated mean rates."""

import json
from collections import Counter
from pathlib import Path

import pytest

import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_arrivals(name):
    a = loadgen.schedule(mix(name), 30, 2**33 + 5, pool_size=16)
    b = loadgen.schedule(mix(name), 30, 2**33 + 5, pool_size=16)
    c = loadgen.schedule(mix(name), 30, 7, pool_size=16)
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    def work(seed):
        s = loadgen.schedule(mix(name), 30, seed, pool_size=16)
        return (Counter((x.task, x.prompt_tokens) for x in s),
                Counter((x.task, x.new_tokens) for x in s))

    assert work(1) == work(2**40 + 3)


@pytest.mark.parametrize("name", MIXES)
def test_mean_rate_and_window(name):
    traffic = mix(name)
    s = loadgen.schedule(traffic, 30, 3, pool_size=16)
    want = sum(loadgen.cumulative_rate(st["arrivals"], 30)
               for st in traffic["streams"])
    assert abs(len(s) - want) <= len(traffic["streams"])
    assert all(0 <= x.due < 30 for x in s)
    assert [x.due for x in s] == sorted(x.due for x in s)


def test_bursty_rate_is_base_plus_bursts():
    arr = {"rate": 5.0, "burst": {"rate": 20.0, "seconds": 1, "every": 5}}
    assert loadgen.cumulative_rate(arr, 5) == pytest.approx(20 + 4 * 5)
    assert loadgen.cumulative_rate(arr, 30) == pytest.approx(6 * 40)
    times = loadgen.arrival_times(arr, 30, __import__("numpy").random
                                  .default_rng(0))
    in_burst = sum(1 for t in times if t % 5 < 1)
    assert in_burst == 6 * 20


def test_shares_and_size_quantiles():
    traffic = mix("vlm-mt.steady")
    s = loadgen.schedule(traffic, 30, 11, pool_size=16)
    tasks = Counter(x.task for x in s)
    assert tasks["caption"] == pytest.approx(0.6 * len(s), abs=1)
    cap = sorted(x.new_tokens for x in s if x.task == "caption")
    assert cap[len(cap) // 2] == pytest.approx(32, abs=1)
    assert min(cap) >= 8 and max(cap) <= 64
    assert {x.prompt_tokens for x in s} <= set(range(8, 33))


@pytest.mark.parametrize("name", MIXES)
def test_each_stratum_holds_the_same_load_for_every_seed(name):
    traffic = mix(name)

    def per_stratum(seed):
        s = loadgen.schedule(traffic, 50, seed, pool_size=16)
        return [sum(1 for x in s if k * 5 <= x.due < (k + 1) * 5)
                for k in range(10)]

    assert per_stratum(4) == per_stratum(2**35 + 1)


def test_spread_order_deals_every_block_across_the_range():
    import numpy as np

    vals = np.arange(100)
    out = loadgen.spread_order(vals, 10, np.random.default_rng(0))
    assert sorted(out) == list(vals)
    for k in range(10):
        block = out[10 * k:10 * (k + 1)]
        assert sorted(v // 10 for v in block) == list(range(10))
