"""The traffic generator: seed replay, the length distributions, the hard
share, and the same work on every seed."""
import collections
import itertools

import numpy as np
import pytest

from bench import spec
from bench import traffic as TR


# the committed mix, and the generator's other kinds built from it
VARIANTS = {
    "mixed.backlog": {},
    "hard.backlog": {"hard_share": 1.0},
    "mixed.open": {"arrival": "poisson", "rate_per_s": 1.35},
}


def mix(name):
    m = spec.load_json(f"{spec.BENCH}/traffic/mixed.backlog.json")
    return dict(m, **VARIANTS[name])


@pytest.mark.parametrize("name", ["mixed.backlog", "hard.backlog", "mixed.open"])
def test_mix_files_validate(name):
    TR.validate(mix(name))


def take(m, seed, n):
    return list(itertools.islice(TR.requests(m, seed), n))


def test_same_seed_replays_bit_for_bit():
    m = mix("mixed.backlog")
    a, b = take(m, 2**31 + 77, 150), take(m, 2**31 + 77, 150)
    assert all(np.array_equal(x.tokens, y.tokens) and x.max_new_tokens == y.max_new_tokens
               and x.hard == y.hard for x, y in zip(a, b))


def test_seeds_replay_the_same_schedule_with_other_tokens():
    m = mix("mixed.backlog")
    n = 3 * m["block"]
    a, b = take(m, 1, n), take(m, 2**31 + 5, n)
    assert [(len(x.tokens), x.max_new_tokens, x.hard) for x in a] == [
        (len(x.tokens), x.max_new_tokens, x.hard) for x in b]
    assert not any(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    # each block holds the same set of shapes, in its own order
    blocks = [a[i:i + m["block"]] for i in range(0, n, m["block"])]
    shapes = [collections.Counter((len(x.tokens), x.max_new_tokens) for x in blk)
              for blk in blocks]
    assert shapes[0] == shapes[1] == shapes[2]
    assert [len(x.tokens) for x in blocks[0]] != [len(x.tokens) for x in blocks[1]]


def test_length_distributions():
    m = mix("mixed.backlog")
    p, o, _ = TR.block_shapes(m, 100)
    mp, mo = m["prompt"], m["output"]
    assert p.min() >= mp["min"] and p.max() <= mp["max"]
    assert o.min() >= mo["min"] and o.max() <= mo["max"]
    assert abs(np.median(p) / mp["median"] - 1) < 0.03
    assert abs(np.median(o) / mo["median"] - 1) < 0.05
    # lognormal sigma: the upper quartile sits at median * e^(sigma * 0.674)
    assert abs(np.quantile(p, 0.75) / mp["median"] - np.exp(mp["sigma"] * 0.674)) < 0.05
    # in the mix's own block no clip binds: every length is the lognormal's
    p, o, _ = TR.block_shapes(m)
    assert p.max() < mp["max"] and o.max() < mo["max"]
    assert p.min() > mp["min"] and o.min() > mo["min"]


def test_block_means_match_the_source():
    """A block's mean lengths are LMSYS-Chat-1M's published means (69.5
    prompt, 214.5 response tokens) within 5%."""
    p, o, _ = TR.block_shapes(mix("mixed.backlog"))
    assert abs(p.mean() / 69.5 - 1) < 0.05 and abs(o.mean() / 214.5 - 1) < 0.05


@pytest.mark.parametrize("name,share", [("mixed.backlog", 0.2), ("hard.backlog", 1.0)])
def test_hard_share_and_token_ranges(name, share):
    m = mix(name)
    items = take(m, 9, 2 * m["block"])
    assert sum(x.hard for x in items) == round(share * len(items))
    for x in items:
        lo, hi = m["hard_ids"] if x.hard else m["easy_ids"]
        assert x.tokens.min() >= lo and x.tokens.max() < hi


def test_open_loop_arrivals():
    m = mix("mixed.open")
    rate = m["rate_per_s"]
    t1 = TR.arrival_times(m, 100.0)
    assert t1 == sorted(t1) and 0 < t1[0] and t1[-1] < 100.0
    assert len(t1) == int(rate * 100.0)
    g1 = np.diff([0.0] + t1)
    assert abs(np.std(g1) / np.mean(g1) - 1.0) < 0.15  # exponential


def test_open_loop_window_is_one_block():
    m = mix("mixed.open")
    n = len(TR.arrival_times(m, 51.0))
    a = list(itertools.islice(TR.requests(m, 5, block=n), n))
    b = list(itertools.islice(TR.requests(m, 6, block=n), n))
    shapes = lambda xs: sorted((len(x.tokens), x.max_new_tokens, x.hard) for x in xs)
    assert shapes(a) == shapes(b) and len(a) == n
    assert sum(x.hard for x in a) == round(m["hard_share"] * n)


def test_closed_loop_concurrency():
    assert TR.outstanding(mix("mixed.backlog"), 8) == 16
