"""The traffic generator and the window arithmetic."""

from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

import chipbench_testcell
from chipbench import stats, traffic
from chipbench.stats import Rec

TRAFFIC_DIR = chipbench_testcell.REPO / "chipbench" / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC_DIR.glob("*.json"))
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3]


def _requests(mix, seed, vocab=1000):
    return traffic.Requests(traffic.load(TRAFFIC_DIR / f"{mix}.json"),
                            vocab, seed)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _requests(mix, SEEDS[2]), _requests(mix, SEEDS[2])
    assert [a.size(i) for i in range(50)] == [b.size(i) for i in range(50)]
    assert a.prompt(3) == b.prompt(3)
    if a.gaps is not None:
        assert np.array_equal(a.arrival_offsets(30.0), b.arrival_offsets(30.0))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes_and_gaps(mix):
    """Seeds reorder one set of sizes and arrival gaps; token ids differ."""
    rs = [_requests(mix, s) for s in SEEDS]
    pool = rs[0].traffic.pool
    sizes = [Counter(r.size(i)[0] for i in range(pool)) for r in rs]
    outs = [Counter(r.size(i)[1] for i in range(pool)) for r in rs]
    assert all(s == sizes[0] for s in sizes)
    assert all(o == outs[0] for o in outs)
    assert [r.size(i) for i in range(pool) for r in rs[:1]] != \
        [r.size(i) for i in range(pool) for r in rs[1:2]]
    assert rs[0].prompt(0) != rs[1].prompt(0)
    if rs[0].gaps is not None:
        assert all(np.allclose(np.sort(r.gaps), np.sort(rs[0].gaps))
                   for r in rs)
        assert rs[0].gaps.mean() == pytest.approx(1 / rs[0].traffic.rate_per_s)


OPEN = [m for m in MIXES if traffic.load(TRAFFIC_DIR / f"{m}.json").loop == "open"]


@pytest.mark.parametrize("mix", OPEN)
@pytest.mark.parametrize("start_s", [0.0, 5.0, 13.7])
def test_a_run_window_holds_the_whole_pool(mix, start_s):
    """With pool = rate x the run's seconds, any window of that length
    holds every size once (to one request at its edges), for every seed."""
    bench = json.loads((chipbench_testcell.REPO / "BENCHMARK.json").read_text())
    seconds = bench["run_seconds"]
    sizes = None
    for seed in SEEDS:
        r = _requests(mix, seed)
        assert r.traffic.pool == round(r.traffic.rate_per_s * seconds)
        t = r.arrival_offsets(start_s + seconds + 60.0)
        idx = np.nonzero((t >= start_s) & (t < start_s + seconds))[0]
        assert abs(len(idx) - r.traffic.pool) <= 1
        got = Counter(r.size(int(i))[0] for i in idx[:r.traffic.pool - 1])
        full = Counter(r.size(i)[0] for i in range(r.traffic.pool))
        assert not got - full and sum((full - got).values()) <= 1
        sizes = sizes or full
        assert full == sizes


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_follow_the_mix(mix):
    r = _requests(mix, 1)
    t = r.traffic
    lens = [r.size(i)[0] for i in range(t.pool)]
    outs = [r.size(i)[1] for i in range(t.pool)]
    assert set(lens) <= set(t.ladder)
    assert min(outs) >= t.output["min"] and max(outs) <= t.output["max"]
    assert np.median(outs) == pytest.approx(t.output["median"], rel=0.1)
    assert len(r.prompt(5)) == r.size(5)[0]
    assert all(1 <= x < 1000 for x in r.prompt(5))


def test_bad_mix_is_refused(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(dict(chipbench_testcell.TRAFFIC, loop="open")))
    with pytest.raises(ValueError):
        traffic.load(p)


def test_percentile_interpolates():
    assert stats.percentile([], 50) is None
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95.0


def _recs():
    return [
        Rec(0, arrival=0.5, prompt_len=8, max_new=3, stamps=[1.2, 1.3, 1.5],
            admitted=1.0, finished=1.5),
        Rec(1, arrival=1.1, prompt_len=8, max_new=4, stamps=[1.4, 1.4, 2.2],
            admitted=1.35),
        # still queued at the close: censored at the time waited so far
        Rec(2, arrival=1.8, prompt_len=8, max_new=4),
        # first token after the close counts as waiting until the close
        Rec(3, arrival=1.9, prompt_len=8, max_new=4, stamps=[2.5],
            admitted=2.1),
    ]


def test_window_counts_tokens_by_stamp_and_requests_by_arrival():
    t_open, t_close = 1.0, 2.0
    recs = _recs()
    assert stats.tokens_in(recs, t_open, t_close) == 5
    assert [r.index for r in stats.arrived_in(recs, t_open, t_close)] == [1, 2, 3]
    assert stats.ttfts(recs, t_open, t_close) == pytest.approx(
        [0.3, 0.2, 0.1])
    assert stats.queue_waits(recs, t_open, t_close) == pytest.approx(
        [0.25, 0.2, 0.1])
    # gaps whose later token lies in the window, the earlier one may not
    assert sorted(stats.token_gaps(recs, t_open, t_close)) == pytest.approx(
        [0.0, 0.1, 0.2])
