"""The general traffic generator: reproducible from its seed, within the
mix's stated parameters and near its source's statistics, Poisson in its
arrivals, and the same sizes for every seed in another order."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import json
import os

import numpy as np
import pytest

from benchlib.traffic import Traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = 2 ** 33 + 17          # a seed past 32 bits


def mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as fh:
        return json.load(fh)


def sessions(name, load, seed, n=200, vocab=4096):
    t = Traffic(mix(name), load, vocab, seed)
    return t, [t.session(k) for k in range(n)]


@pytest.mark.parametrize("name,load", [("longdoc-closed", {"clients": 4}),
                                       ("sessions-open", {"session_rate_per_s": 3.0})])
def test_same_seed_same_traffic(name, load):
    _, a = sessions(name, load, BIG)
    _, b = sessions(name, load, BIG)
    assert [(s.arrival, s.system, s.users, s.replies, s.thinks) for s in a] == \
           [(s.arrival, s.system, s.users, s.replies, s.thinks) for s in b]
    _, c = sessions(name, load, BIG + 1)
    assert [s.users for s in a] != [s.users for s in c]


def test_longdoc_within_its_parameters():
    m = mix("longdoc-closed")
    t, ss = sessions("longdoc-closed", {"clients": 4}, 5, n=2000)
    assert t.loop == "closed" and t.clients == 4
    for s in ss:
        assert s.arrival is None and s.system == [] and len(s.users) == 1
        assert m["reply"]["lo"] <= s.replies[0] <= m["reply"]["hi"]
        n = len(s.users[0])
        assert m["prompt"]["lo"] <= n <= m["prompt"]["hi"]
        assert n + s.replies[0] <= m["max_context"]         # a long document is cut
        assert all(0 < tok < 4096 for tok in s.users[0])
    # the source's medians, read from 2000 draws
    assert np.median([len(s.users[0]) for s in ss]) == pytest.approx(m["prompt"]["median"], rel=0.08)
    assert np.median([s.replies[0] for s in ss]) == pytest.approx(m["reply"]["median"], rel=0.08)


def test_sessions_within_their_parameters():
    m = mix("sessions-open")
    t, ss = sessions("sessions-open", {"session_rate_per_s": 3.0}, 9, n=2000)
    assert len(t.system_prompts) == m["system_prompts"]["count"]
    assert all(len(p) == m["system_prompts"]["tokens"] for p in t.system_prompts)
    arrivals = [s.arrival for s in ss]
    assert arrivals[0] == 0.0 and all(b > a for a, b in zip(arrivals, arrivals[1:]))
    counts = np.bincount([t.system_prompts.index(s.system) for s in ss])
    assert counts[0] > counts[-1]                    # Zipf: the first is the most popular
    for s in ss:
        assert 1 <= len(s.users) <= m["turns"]["hi"]
        assert len(s.thinks) == len(s.users) - 1 and all(x >= 0 for x in s.thinks)
        for u, r in zip(s.users, s.replies):
            assert m["user"]["lo"] <= len(u) <= m["user"]["hi"]
            assert m["reply"]["lo"] <= r <= m["reply"]["hi"]
    # the source's means, read from 2000 sessions
    assert np.mean([len(s.users) for s in ss]) == pytest.approx(m["turns"]["mean"], rel=0.06)
    users = [len(u) for s in ss for u in s.users]
    replies = [r for s in ss for r in s.replies]
    assert np.mean(users) == pytest.approx(m["user"]["mean"], rel=0.08)
    assert np.mean(replies) == pytest.approx(m["reply"]["mean"], rel=0.08)


def test_open_loop_arrivals_are_poisson():
    """Gaps at the cell's rate, as bursty as a Poisson process: coefficient
    of variation about 1, and some gaps many times the mean."""
    t, _ = sessions("sessions-open", {"session_rate_per_s": 3.0}, 9, n=1)
    gaps = np.diff([t.arrival(k) for k in range(2001)])
    assert np.mean(gaps) == pytest.approx(1 / 3.0, rel=0.08)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)
    assert np.max(gaps) > 5 / 3.0


def test_sizes_are_draws_not_quantiles():
    """Blocks of the trace differ from one another: the sizes are random
    draws, not the same few values in every block."""
    t, ss = sessions("longdoc-closed", {"clients": 4}, 5, n=400)
    assert len({len(s.users[0]) for s in ss}) > 200
    assert t.block_shapes(0) != t.block_shapes(1)


@pytest.mark.parametrize("name,load", [("longdoc-closed", {"clients": 4}),
                                       ("sessions-open", {"session_rate_per_s": 3.0})])
def test_every_seed_gets_the_same_sizes_in_another_order(name, load):
    def shape(s):
        return (len(s.users), tuple(len(u) for u in s.users), tuple(s.replies),
                tuple(s.thinks), len(s.system))

    ta, a = sessions(name, load, 1, n=64)
    tb, b = sessions(name, load, BIG, n=64)
    n = ta.block
    for k in range(0, 64, n):           # every block: the same sessions
        assert sorted(map(shape, a[k:k + n])) == sorted(map(shape, b[k:k + n]))
    assert list(map(shape, a)) != list(map(shape, b))        # in another order
    if ta.loop == "open":
        gaps = lambda t: sorted(np.diff([t.arrival(k) for k in range(n + 1)]).round(9))  # noqa: E731
        assert gaps(ta) == gaps(tb)
        assert ta.arrival(4 * n) == pytest.approx(tb.arrival(4 * n))
