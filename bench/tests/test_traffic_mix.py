"""The copied generators: the same seed gives the same trace, and every
seed gets the same set of session lengths."""
import numpy as np

from bench import traffic

BIG = 2**31 + 12345


def test_arrivals_repeat_for_a_seed_and_differ_across_seeds():
    mix = traffic.load("imix-steady")
    a = traffic.arrivals(mix, BIG, 3.0)
    b = traffic.arrivals(mix, BIG, 3.0)
    c = traffic.arrivals(mix, BIG + 1, 3.0)
    np.testing.assert_array_equal(a, b)
    assert len(a) != len(c) or not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 3.0
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(a) - 3.0 * rate) < 6 * np.sqrt(3.0 * rate)


def test_burst_sizes_depend_only_on_seed_and_index():
    mix = traffic.load("imix-steady")
    s1 = traffic.BurstSizes(mix, BIG)
    s2 = traffic.BurstSizes(mix, BIG)
    rows = [s1.row(i) for i in (0, 1, 1023, 1024, 5000)]
    for i, r in zip((5000, 1024, 1023, 1, 0), reversed(rows)):
        np.testing.assert_array_equal(s2.row(i), r)
    assert rows[0].shape == (mix["burst"],)
    assert not np.array_equal(traffic.BurstSizes(mix, BIG + 1).row(0), rows[0])
    big = np.concatenate([s1.row(i) for i in range(3000)])
    share = {v: np.mean(big == v) for v in mix["sizes"]["values"]}
    w = np.asarray(mix["sizes"]["weights"], float)
    for v, p in zip(mix["sizes"]["values"], w / w.sum()):
        assert abs(share[v] - p) < 0.01


def test_sessions_are_one_set_in_a_seeded_order():
    mix = traffic.load("sessions-long")
    a = traffic.session_tokens(mix, BIG)
    b = traffic.session_tokens(mix, BIG)
    c = traffic.session_tokens(mix, 7)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(c))
    s = mix["sessions"]
    assert len(a) == s["count"]
    assert a.min() >= s["min_tokens"] and a.max() <= s["max_tokens"]


def test_zipf_quantiles_follow_the_pmf():
    z = traffic.ZipfLengths(1.1, 1024, 4096)
    q = z.quantiles(1000)
    assert q[0] == 1024 and q[-1] <= 4096
    assert np.mean(q == 1024) > 0.15  # rank 1 holds about 16% of the mass
    assert np.all(np.diff(q) >= 0)
