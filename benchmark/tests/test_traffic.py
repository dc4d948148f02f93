"""The general generator: the seed orders the work and draws its tokens; the
set of sizes and gaps a window meets is the mix's, for every seed."""
import numpy as np

from benchmark.harness import stats, traffic

MIX = {"prompt": {"dist": "lognormal", "median": 400, "sigma": 0.9,
                  "min": 32, "max": 1536},
       "output": {"dist": "lognormal", "median": 100, "sigma": 0.7,
                  "min": 8, "max": 384}}


def _shape(reqs):
    return [(len(r.prompt), r.n_out, round(r.due_s, 9)) for r in reqs]


def test_the_seed_draws_the_schedule_and_the_tokens():
    a = traffic.generate(MIX, 1, 120, 1000, 40.0)
    b = traffic.generate(MIX, 2**31 + 12345, 120, 1000, 40.0)
    # another seed is another order of arrivals and sizes, other tokens
    assert _shape(a) != _shape(b)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    # the same seed, the same inputs
    c = traffic.generate(MIX, 1, 120, 1000, 40.0)
    assert _shape(a) == _shape(c) and [r.prompt for r in a] == \
        [r.prompt for r in c]


def test_every_seed_meets_the_same_set_of_sizes_and_gaps():
    a = traffic.generate(MIX, 3, 41, 1000, 51.0)
    b = traffic.generate(MIX, 3000000004, 41, 1000, 51.0)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.n_out for r in a) == sorted(r.n_out for r in b)
    ga = np.diff([0.0] + [r.due_s for r in a])[1:]
    gb = np.diff([0.0] + [r.due_s for r in b])[1:]
    # all gaps but the first (halved) are the same set, in another order
    assert abs(np.sort(ga)[5:].sum() - np.sort(gb)[5:].sum()) < 1.0
    # prompt and answer lengths are ordered independently of each other
    assert [len(r.prompt) for r in a] != sorted(len(r.prompt) for r in a)


def _sixths(reqs):
    """Prompt tokens in each sixth of the window's requests, largest over
    smallest."""
    plen = np.array([len(r.prompt) for r in reqs])
    sums = [part.sum() for part in np.array_split(plen, 6)]
    return max(sums) / min(sums)


def test_an_order_block_deals_every_part_of_the_window_an_even_sample():
    """`order_block`: the same set of sizes and gaps as the plain order,
    dealt so that every hand of consecutive requests holds its share of
    the long prompts and of the short gaps; inside a hand any order."""
    n, span = 102, 51.0
    seeds = (11, 2**31 + 99, 3000000004, 5, 6, 7)
    plain = [traffic.generate(MIX, s, n, 1000, span) for s in seeds]
    even = [traffic.generate({**MIX, "order_block": 8}, s, n, 1000, span)
            for s in seeds]
    for a, b in zip(plain, even):
        assert sorted(len(r.prompt) for r in b) == \
            sorted(len(r.prompt) for r in a)
        assert sorted(r.n_out for r in b) == sorted(r.n_out for r in a)
        due = [r.due_s for r in b]
        assert due == sorted(due) and 0 < due[0] and due[-1] < span
        assert _shape(a) != _shape(b)
        # a sixth of the requests carries about a sixth of the prompt tokens
        assert _sixths(b) < 1.5
    assert _shape(even[0]) != _shape(even[1])   # another seed, another deal
    assert max(_sixths(a) for a in plain) > 1.8     # the plain order: any
    # a block that leaves fewer than two hands is the plain order
    assert _shape(traffic.generate({**MIX, "order_block": 60}, 11, n, 1000,
                                   span)) == _shape(plain[0])


def test_lengths_and_due_times_inside_their_limits():
    reqs = traffic.generate(MIX, 7, 200, 500, 50.0)
    assert all(32 <= len(r.prompt) <= 1536 and 8 <= r.n_out <= 384
               for r in reqs)
    assert all(1 <= t < 500 for r in reqs for t in r.prompt)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 50.0
    med = stats.median([len(r.prompt) for r in reqs])
    assert 380 < med < 420
    # a Poisson process's gaps: their spread is about their mean
    g = np.diff(due)
    assert 0.8 < g.std() / g.mean() < 1.1


def test_a_closed_loop_has_no_due_times():
    reqs = traffic.generate({**MIX, "output": {"dist": "uniform", "min": 8,
                                               "max": 32}}, 5, 66, 1000)
    assert all(r.due_s == 0.0 for r in reqs)
    assert sorted(set(r.n_out for r in reqs)) == list(range(8, 33))


def test_percentile_is_a_measured_sample():
    assert stats.percentile([], 90) is None
    assert stats.percentile(list(range(1, 101)), 90) == 90
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 100], 90) == 9


def test_time_per_token_is_read_over_whole_blocks():
    # first token alone, then four at a time: one host read a block
    t = [0.0, 1.0, 1.0, 1.0, 1.0, 3.0, 3.0, 3.0, 3.0, 4.0, 4.0]
    assert stats.block_times(t, 4) == [0.25, 0.5]
    # a larger hand-out cannot hide a stall: blocks of 4 over reads of 8
    t8 = [0.0] + [2.0] * 8 + [4.0] * 8
    assert stats.block_times(t8, 4) == [0.5, 0.0, 0.5, 0.0]
    assert stats.block_times([0.0, 1.0], 4) == []
