"""The generator: one multiset for every seed, one schedule per seed."""
import collections

import numpy as np
import pytest

from benchmarks.onchip import spec, traffic


def _mix(name):
    return spec.load_cell(name).traffic


@pytest.mark.parametrize("cell", ["smollm360m.chat", "mingru360m.longdoc"])
def test_same_multiset_for_every_seed(cell):
    t = _mix(cell)
    rate = spec.load_cell(cell).cell.get("rate_per_s")
    n = t["requests"]
    seen = []
    for seed in (0, 1, 2**31 + 7, -5, 2**70):
        s = traffic.schedule(t, seed, 40, 49152, rate)
        seen.append((collections.Counter(map(len, s.prompts[:n])),
                     collections.Counter(s.out_lens[:n].tolist()),
                     np.sort(np.diff(np.concatenate([[0.0], s.due]))[:n])))
    for other in seen[1:]:
        assert other[0] == seen[0][0]
        assert other[1] == seen[0][1]
        np.testing.assert_allclose(other[2], seen[0][2])


@pytest.mark.parametrize("cell", ["smollm360m.chat", "mingru360m.longdoc"])
def test_same_schedule_for_the_same_seed(cell):
    t = _mix(cell)
    rate = spec.load_cell(cell).cell.get("rate_per_s")
    a = traffic.schedule(t, 123, 40, 49152, rate)
    b = traffic.schedule(t, 123, 40, 49152, rate)
    c = traffic.schedule(t, 124, 40, 49152, rate)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    np.testing.assert_array_equal(a.due, b.due)
    assert [len(p) for p in a.prompts] != [len(p) for p in c.prompts]


def test_repeats_keep_equal_lengths_a_multiset_apart():
    t = _mix("smollm360m.chat")
    s = traffic.schedule(t, 5, 200, 49152, rate=4.0)
    n = t["requests"]
    lens = [len(p) for p in s.prompts]
    assert len(lens) > n and lens[n:2 * n] == lens[:n]
    assert len(set(lens[:n])) == n


def test_lengths_respect_the_mix():
    t = _mix("smollm360m.chat")
    m = traffic.multiset(t, 400, rate=2.0)
    assert len(set(m.prompt_lens.tolist())) == 400        # distinct
    assert (m.prompt_lens + m.out_lens <= t["max_total"]).all()
    assert m.prompt_lens.min() >= t["prompt"]["min"]
    assert t["output"]["min"] <= m.out_lens.min()
    assert m.out_lens.max() <= t["output"]["max"]
    assert 800 <= np.median(m.prompt_lens) <= 1100
    assert 100 <= np.median(m.out_lens) <= 160
    np.testing.assert_allclose(m.gaps.mean(), 1 / 2.0, rtol=0.05)


def test_closed_loop_mix_leans_long():
    t = _mix("mingru360m.longdoc")
    m = traffic.multiset(t, int(t["requests"]))
    assert m.gaps.size == 0
    assert np.median(m.prompt_lens) > 1600
    assert (m.prompt_lens + m.out_lens <= t["max_total"]).all()
