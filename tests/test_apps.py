"""Workload apps: nqueens, qsort, cilksort, FFT (+ perf-regression harness).

These are the reference's performance-regression suite apps (BASELINE.md
rows; test/performance-regression/full-apps/) implemented against the new
API; every run() self-checks its output.
"""

import subprocess
import sys

import numpy as np
from conftest import CHILD_SECONDS

import hclib_tpu as hc
from hclib_tpu.models import fft, nqueens, sort


def test_nqueens_counts():
    for n in (5, 6, 8):
        r = nqueens.run(n, nworkers=4)
        assert r["value"] == nqueens.KNOWN_COUNTS[n]


def test_nqueens_cutoff_variants():
    assert nqueens.run(7, cutoff=1, nworkers=2)["value"] == 40
    assert nqueens.run(7, cutoff=7, nworkers=2)["value"] == 40


def test_qsort_sorts():
    r = sort.run(1 << 14, "qsort", threshold=512, nworkers=4)
    assert r["keys_per_sec"] > 0


def test_qsort_adversarial_inputs():
    for arr in (
        np.zeros(5000, np.int64),
        np.arange(5000, dtype=np.int64),
        np.arange(5000, dtype=np.int64)[::-1].copy(),
    ):
        expect = np.sort(arr.copy())
        hc.launch(sort.qsort_par, arr, 256, nworkers=4)
        np.testing.assert_array_equal(arr, expect)


def test_cilksort_sorts():
    r = sort.run(1 << 14, "cilksort", threshold=512, nworkers=4)
    assert r["keys_per_sec"] > 0


def test_cilksort_non_power_of_four():
    arr = np.random.default_rng(1).integers(0, 1000, 10_000).astype(np.int64)
    expect = np.sort(arr.copy())
    hc.launch(sort.cilksort, arr, 333, nworkers=4)
    np.testing.assert_array_equal(arr, expect)


def test_fft_matches_numpy():
    r = fft.run(1 << 12, threshold=1 << 9, nworkers=4)
    assert r["rel_err"] < 1e-8


def test_fft_device_path():
    r = fft.run(1 << 10, device=True)
    assert r["rel_err"] < 1e-2


def test_fft_rejects_non_power_of_two():
    import pytest

    with pytest.raises(ValueError):
        fft.fft_par(np.zeros(100))


def test_perf_regression_harness_quick(tmp_path):
    out = subprocess.run(
        [sys.executable, "tools/perf_regression.py", "--quick", "--trials", "1",
         "--log-dir", str(tmp_path),
         "--apps", "fib,nqueens,qsort,cilksort,fft,fib-ddt"],
        capture_output=True, text=True, timeout=CHILD_SECONDS,
    )
    assert out.returncode == 0, out.stderr
    assert "fib" in out.stdout and "log written" in out.stdout
    # second run compares against the first
    out2 = subprocess.run(
        [sys.executable, "tools/perf_regression.py", "--quick", "--trials", "1",
         "--log-dir", str(tmp_path), "--tolerance", "1000", "--apps", "fib"],
        capture_output=True, text=True, timeout=CHILD_SECONDS,
    )
    assert out2.returncode == 0, out2.stderr
    assert "vs prev" in out2.stdout
