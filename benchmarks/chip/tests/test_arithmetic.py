import os
import statistics

import numpy as np
import pytest

from harness.common import BENCH_DIR, Run, load_module, percentile, spread


def reader(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 1001):
        xs = list(rng.random(n))
        for q in (50, 90, 99):
            assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([], 99) is None


def test_spread_is_iqr_over_median():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert spread(v) == pytest.approx((q3 - q1) / med)


def _lm_run():
    run = Run(cell={}, config={}, traffic={}, t0=10.0, t1=20.0)
    run.requests = [
        # arrived before the window: no TTFT; its gaps ending inside count
        dict(t_arrive=9.0, t_admit=9.1, times=[9.5, 10.5, 11.0]),
        dict(t_arrive=11.0, t_admit=11.2, times=[11.5, 11.6, 11.8]),
        # still waiting at the window's end: enters with its wait so far
        dict(t_arrive=15.0, t_admit=None, times=[]),
        # after the window: left out
        dict(t_arrive=21.0, t_admit=None, times=[]),
    ]
    return run


def test_censored_ttft_and_admission():
    run = _lm_run()
    ttft = [0.5, 5.0]                # 11.5 - 11.0; 20.0 - 15.0
    assert reader("ttft_p90_ms").read(run) == pytest.approx(
        1e3 * np.percentile(ttft, 90))
    waits = [0.2, 5.0]
    assert reader("lm_admit_wait_p90_ms").read(run) == pytest.approx(
        1e3 * np.percentile(waits, 90))


def test_itl_gaps_that_end_in_window():
    run = _lm_run()
    gaps = [1.0, 0.5, 0.1, 0.2]      # 9.5->10.5 ends inside; the rest
    assert reader("itl_p99_ms").read(run) == pytest.approx(
        1e3 * np.percentile(gaps, 99))


def test_images_per_s_and_image_tail():
    run = Run(cell={}, config={}, traffic={}, t0=0.0, t1=2.0)
    run.requests = [dict(t_arrive=t, t_done=t + 0.01 * (i % 5),
                         queue_wait_s=0.0, service_s=0.01)
                    for i, t in enumerate(np.linspace(-0.5, 2.5, 31))]
    done = sum(1 for r in run.requests if 0.0 <= r["t_done"] <= 2.0)
    assert reader("images_per_s").read(run) == pytest.approx(done / 2.0)
    lat = [r["t_done"] - r["t_arrive"] for r in run.requests
           if 0.0 <= r["t_arrive"] <= 2.0]
    assert reader("image_p99_ms").read(run) == pytest.approx(
        1e3 * np.percentile(lat, 99))

