"""bench.py stalled-window annotation: wall-time
outlier windows are flagged in the JSON so cross-round ci95 comparisons
can exclude host stalls; raw windows stay untouched."""

import importlib.util
import os

import numpy as np


def _load_bench():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(root, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestAnnotateStalledWindows:
    def test_flags_single_stall(self):
        bench = _load_bench()
        # The recorded shape: nine ~6.6 s windows, one 16.7 s stall.
        windows = [6.6, 6.5, 6.7, 6.6, 6.4, 16.7, 6.6, 6.5, 6.7, 6.6]
        stalled, ok = bench.annotate_stalled_windows(windows)
        assert stalled == [5]
        assert len(ok) == 9 and 5 not in ok

    def test_clean_run_flags_nothing(self):
        bench = _load_bench()
        stalled, ok = bench.annotate_stalled_windows(
            [6.6, 6.5, 6.7, 6.6, 6.55])
        assert stalled == []
        assert ok == [0, 1, 2, 3, 4]

    def test_uniformly_slow_run_is_not_stalled(self):
        """A run that is slow everywhere has no outliers to trim —
        flagging every window would silently empty the trimmed stats."""
        bench = _load_bench()
        stalled, ok = bench.annotate_stalled_windows([60.0])
        assert stalled == []
        assert ok == [0]

    def test_trimmed_ci_recovers(self):
        """The motivating number: one stall blows the naive ci95 by two
        orders of magnitude; the trimmed CI stays at the clean run's
        scale."""
        bench = _load_bench()
        rates = [2500, 2510, 2490, 2505, 613, 2495, 2508, 2502, 2498,
                 2506]
        walls = [6.6, 6.6, 6.6, 6.6, 16.7, 6.6, 6.6, 6.6, 6.6, 6.6]
        stalled, ok = bench.annotate_stalled_windows(walls)
        assert stalled == [4]
        full_ci = 1.96 * np.std(rates)
        trimmed_ci = 1.96 * np.std([rates[i] for i in ok])
        assert full_ci > 50 * trimmed_ci

    def test_custom_factor(self):
        bench = _load_bench()
        windows = [1.0, 1.0, 1.0, 1.4]
        assert bench.annotate_stalled_windows(windows)[0] == []
        assert bench.annotate_stalled_windows(windows,
                                              stall_factor=1.3) == (
            [3], [0, 1, 2])
