"""The generator: one seed, one traffic; every seed the same sizes."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness.traffic import Profile, Traffic, sizes

MIXES = sorted(p.stem for p in (Path(__file__).parents[1] / "traffic").glob("*.json"))


def mix(name):
    return json.loads((Path(__file__).parents[1] / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = Traffic(mix(name), 2 ** 31 + 17, 1000), Traffic(mix(name), 2 ** 31 + 17, 1000)
    for i in (0, 1, 77):
        da, pa, oa = a.request(i)
        db, pb, ob = b.request(i)
        assert da == db and oa == ob and np.array_equal(pa, pb)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes(name):
    """Every seed serves the same sizes, in an order and (open loop) at
    arrival times of its own, with tokens of its own."""
    a, b = Traffic(mix(name), 1, 1000), Traffic(mix(name), 2, 1000)
    n = a.n
    la = [len(a.request(i)[1]) for i in range(n)]
    lb = [len(b.request(i)[1]) for i in range(n)]
    assert sorted(la) == sorted(lb)
    assert la[:50] != lb[:50]
    assert not np.array_equal(a.request(0)[1], b.request(0)[1])
    if a.loop == "open":
        assert [a.due(i) for i in range(n)] != [b.due(i) for i in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_clipped_and_fit_the_cache(name):
    m = mix(name)
    s = sizes(m)
    for part in ("prompt", "output"):
        assert s[part].min() >= m[part]["min"] and s[part].max() <= m[part]["max"]
    t = Traffic(m, 5, 1000)
    for i in range(200):
        _, prompt, out = t.request(i)
        assert len(prompt) - 1 + out <= m["serving"]["max_len"]
        assert prompt.min() >= 1 and prompt.max() < 1000


def test_open_loop_rate_and_prefix_sharing():
    m = mix("chat")
    t = Traffic(m, 2 ** 31 + 9, 1000)
    n, k = t.n, m["arrivals"]["block"]
    due = np.array([t.due(i) for i in range(2 * n)])
    assert np.all(np.diff(due) >= 0)
    # every block of k requests arrives inside its own k / rate seconds
    blk = np.arange(2 * n) // k
    assert np.all(due >= blk * k / m["rate_per_s"])
    assert np.all(due < (blk + 1) * k / m["rate_per_s"])
    heads = {tuple(t.request(i)[1][:m["shared_prefix"]["length"]]) for i in range(100)}
    assert len(heads) <= m["shared_prefix"]["count"]


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 40])
def test_open_loop_blocks_hold_one_of_each_stratum(seed):
    """Each block offers one request of each stratum by output length, so
    every block offers about the same work, whatever the seed."""
    m = mix("chat")
    t = Traffic(m, seed, 1000)
    k = m["arrivals"]["block"]
    per = t.n // k
    rank = np.empty(t.n, np.int64)
    rank[np.argsort(t.output, kind="stable")] = np.arange(t.n)
    for b in range(per):
        strata = sorted(rank[t.order[b * k:(b + 1) * k]] // per)
        assert strata == list(range(k))


def test_profile_keeps_the_mean_rate_in_bursts():
    p = Profile([[2, 3.0], [4, 0.0]])
    # 6 s of operational time pass in the 2-s burst at three times the rate
    assert p.wall(0.0) == 0.0 and p.wall(3.0) == pytest.approx(1.0)
    assert p.wall(5.7) == pytest.approx(1.9)
    assert p.wall(6.5) == pytest.approx(6.0 + 0.5 / 3)
    assert Profile(None).wall(7.25) == pytest.approx(7.25)


def test_open_loop_window_holds_the_same_sizes_for_every_seed():
    """The chat mix's ramp and window are whole blocks: at the benchmark's
    run length every seed's window is due the whole multiset once, in an
    order and at times of its own."""
    m = mix("chat")
    bm = json.loads((Path(__file__).parents[2] / "BENCHMARK.json").read_text())
    w0, w1 = m["ramp_s"], m["ramp_s"] + bm["run_seconds"]
    seen = []
    for seed in (1, 2 ** 31 + 3):
        t = Traffic(m, seed, 1000)
        due = [i for i in range(3 * t.n) if w0 <= t.due(i) < w1]
        seen.append((sorted(int(t.prompt[t.order[i % t.n]]) for i in due),
                     sorted(int(t.output[t.order[i % t.n]]) for i in due),
                     [t.due(i) for i in due]))
    assert seen[0][0] == seen[1][0] == sorted(int(x) for x in t.prompt)
    assert seen[0][1] == seen[1][1]
    assert seen[0][2] != seen[1][2]
