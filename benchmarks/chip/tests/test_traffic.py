import numpy as np
import pytest

from harness import traffic as T

CHAT = {"loop": "open", "rate_per_s": 3.0, "warm_in_s": 5.0,
        "prompt_len": {"median": 512, "sigma": 0.8, "min": 64, "max": 1536},
        "output_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512}}


def test_same_seed_same_traffic():
    a = T.open_schedule(CHAT, 30.0, np.random.default_rng(7))
    b = T.open_schedule(CHAT, 30.0, np.random.default_rng(7))
    np.testing.assert_array_equal(a.offsets, b.offsets)
    ra = T.lm_requests(CHAT, 64, 1000, np.random.default_rng(7))
    rb = T.lm_requests(CHAT, 64, 1000, np.random.default_rng(7))
    for (pa, oa), (pb, ob) in zip(ra, rb):
        np.testing.assert_array_equal(pa, pb)
        assert oa == ob


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = T.open_schedule(CHAT, 60.0, np.random.default_rng(1))
    b = T.open_schedule(CHAT, 60.0, np.random.default_rng(2))
    whole = len(a.offsets) // T.BLOCK * T.BLOCK
    ga = np.diff(a.offsets, prepend=0)[:whole]
    gb = np.diff(b.offsets, prepend=0)[:whole]
    assert not np.array_equal(ga, gb)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    # whole blocks span the same time, so the offered rate is exact
    k = T.BLOCK
    assert abs(a.offsets[k - 1] - b.offsets[k - 1]) < 1e-9
    assert abs(k / a.offsets[k - 1] / CHAT["rate_per_s"] - 1) < 0.05
    la = [len(p) for p, _ in T.lm_requests(CHAT, 128, 10, np.random.default_rng(1))]
    lb = [len(p) for p, _ in T.lm_requests(CHAT, 128, 10, np.random.default_rng(2))]
    assert la != lb and sorted(la) == sorted(lb)


def test_lengths_keep_median_and_clip():
    n = T.lognormal_lengths(CHAT["prompt_len"], 640, np.random.default_rng(0))
    assert n.min() >= 64 and n.max() <= 1536
    assert abs(np.median(n) - 512) <= 16


def test_schedule_covers_warm_in_and_window():
    s = T.open_schedule(CHAT, 51.0, np.random.default_rng(3))
    assert s.offsets[-1] > CHAT["warm_in_s"] + 51.0


def test_lateness_report():
    s = T.Arrivals(np.zeros(3))
    assert s.report() == {}
    s.late.extend([0.001, 0.002, 0.010])
    r = s.report()
    assert r["submitted"] == 3
    assert abs(r["late_max_ms"] - 10.0) < 1e-9
    assert abs(r["late_p50_ms"] - 2.0) < 1e-9


def test_fixed_order_is_the_same_for_every_seed_and_spreads_prefixes():
    mix = dict(CHAT, block=16, order="fixed")
    ra = T.lm_requests(mix, 40, 1000, np.random.default_rng(1))
    rb = T.lm_requests(mix, 40, 1000, np.random.default_rng(2))
    assert [(len(p), o) for p, o in ra] == [(len(p), o) for p, o in rb]
    assert not np.array_equal(ra[0][0], rb[0][0])     # contents differ
    one = T.lognormal_lengths(CHAT["prompt_len"], 16, None, 16, 2)
    lens = [len(p) for p, _ in ra]
    # each block of 16 holds the 16 quantiles, in the same order
    assert sorted(lens[:16]) == sorted(one) and lens[16:32] == lens[:16]
    # every prefix of 2**k spreads over the whole distribution
    for k in (2, 4, 8):
        q = sorted(T.fixed_order(16, 2)[:k])
        assert q == list(range(0, 16, 16 // k))
    # prompts and answers follow different orders, so they are not paired
    # short with short
    outs = [o for _, o in ra[:16]]
    assert np.argsort(lens[:16]).tolist() != np.argsort(outs).tolist()


def test_unknown_order_is_refused():
    with pytest.raises(ValueError):
        T.lm_requests(dict(CHAT, order="sorted"), 4, 10,
                      np.random.default_rng(0))
