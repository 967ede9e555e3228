"""The benchmark's arithmetic: percentiles, rates, the supported tail."""

import statistics

import pytest

from benchmark import harness


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 50, 5.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 90, 4.6),
    ([4.0, 1.0, 3.0, 2.0], 0, 1.0),
    ([4.0, 1.0, 3.0, 2.0], 100, 4.0),
])
def test_percentile_interpolates_over_all_samples(values, q, want):
    assert harness.percentile(values, q) == pytest.approx(want)


def test_percentile_matches_numpy_and_median():
    import numpy as np

    rng = np.random.default_rng(7)
    xs = rng.normal(127.0, 3.0, size=315).tolist()
    for q in (50, 90, 95):
        assert harness.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert harness.percentile(xs, 50) == pytest.approx(statistics.median(xs))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_rate_is_work_over_time_and_refuses_no_time():
    assert harness.rate(2032, 4.0) == 508.0
    with pytest.raises(ValueError):
        harness.rate(10, 0.0)


@pytest.mark.parametrize("n,want", [
    (5, None), (20, 50), (100, 90), (199, 90), (200, 95), (1000, 99),
])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert harness.highest_supported_percentile(n) == want


def test_counter_sum_filters_by_label():
    c = {'f_total{backend="pallas",algo="ed25519"}': 3.0,
         'f_total{backend="host",algo="ed25519"}': 2.0, "g_total": 7.0}
    assert harness.counter_sum(c, "f_total") == 5.0
    assert harness.counter_sum(c, "f_total", {"backend": "host"}) == 2.0
    assert harness.counter_sum(c, "g_total") == 7.0
    assert harness.counter_sum(c, "h_total") == 0.0


def test_gc_watch_counts_a_full_collection_in_its_tenth_of_the_window():
    import gc
    import time

    watch = harness.GcWatch()
    gc.callbacks.append(watch)
    t0 = time.perf_counter()
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(watch)
    t1 = time.perf_counter()
    assert watch.count[2] == 1 and watch.seconds[2] > 0
    # the collection began in the window's first half: placed by its start
    line = watch.line(t0, t0 + 2 * (t1 - t0), unfrozen=7)
    assert "gen2=1/" in line and "unfrozen_at_start=7" in line
    tenths = eval(line.split("full_by_tenth=")[1].split(" unfrozen")[0])
    assert sum(tenths) == 1 and sum(tenths[:5]) == 1
