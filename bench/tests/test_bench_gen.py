"""The traffic generator: every seed gets the same work in another order."""
import numpy as np
import pytest

from bench import gen


@pytest.mark.parametrize("mix", ["chat", "longctx"])
def test_seeds_share_the_multiset(mix):
    m = gen.load_mix(mix)
    a = gen.make_requests(m, 1, 40, 1000, rate_per_s=0.5)
    b = gen.make_requests(m, 2**40 + 7, 40, 1000, rate_per_s=0.5)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    gaps = [np.diff([0.0] + [r.due_s for r in x]) for x in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert all(0 <= t < 1000 for r in a for t in r.prompt)


def test_open_loop_arrivals_span_the_window():
    m = gen.load_mix("chat")
    n = gen.count_for(m, {"rate_per_s": 0.5}, 45)
    reqs = gen.make_requests(m, 3, n, 1000, rate_per_s=0.5)
    due = [r.due_s for r in reqs]
    assert n == 23 and due[0] > 0.0 and due == sorted(due)
    # the exponential's quantile gaps sum to about n / rate, whatever the
    # order: the last arrival of every seed comes at the same time
    assert 40 < due[-1] <= n / 0.5


def test_lengths_follow_the_mix():
    spec = gen.load_mix("chat")["prompt"]
    x = gen.lengths(spec, 101)
    assert x[50] == spec["median"]
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    with pytest.raises(ValueError, match="needs the cell's rate"):
        gen.make_requests(gen.load_mix("chat"), 1, 3, 10)
