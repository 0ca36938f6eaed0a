"""Workload model tests (reference acceptance suite at test sizes)."""

import numpy as np
import pytest

import hclib_tpu as hc
from hclib_tpu.models import arrayadd, cholesky, fib, smithwaterman, uts


def test_fib_finish():
    r = fib.run(16, variant="finish", nworkers=3)
    assert r["value"] == 987


def test_fib_finish_cutoff():
    r = fib.run(20, variant="finish", nworkers=3, cutoff=10)
    assert r["value"] == 6765


def test_fib_ddf():
    r = fib.run(16, variant="ddf", nworkers=3)
    assert r["value"] == 987


def test_uts_t3_parallel_matches_sequential():
    seq = uts.count_seq(uts.T_TINY)
    par = uts.count_parallel(uts.T_TINY, nworkers=4)
    assert par == seq
    assert seq[0] == 1279  # pinned: detects any RNG/shape drift


def test_uts_grain_batching():
    seq = uts.count_seq(uts.T_TINY)
    assert uts.count_parallel(uts.T_TINY, nworkers=4, grain=32) == seq


def test_uts_canonical_root_children():
    """The canonical trees' first-level structure is fixed by the SHA-1 RNG;
    T1 root (seed 19, b0=4) child count is deterministic."""
    s = uts.root_state(uts.T1.root_seed)
    n = uts.num_children(uts.T1, s, 0)
    assert 0 <= n <= 100
    # Re-derivation must be stable.
    assert n == uts.num_children(uts.T1, s, 0)


def test_cholesky_small():
    r = cholesky.run(n=128, tile=32)
    assert r["ok"], r


def test_cholesky_uneven_rejected():
    a = cholesky.make_spd(100)
    with pytest.raises(ValueError):
        cholesky.cholesky_tiled(a, 32)


def test_smithwaterman_matches_sequential():
    a = smithwaterman.random_seq(150, 1)
    b = smithwaterman.random_seq(130, 2)
    h_par = smithwaterman.sw_tiled(a, b, tile=32)
    h_seq = smithwaterman.sw_seq(a, b)
    assert np.array_equal(h_par, h_seq)


def test_arrayadd_models():
    arrayadd.arrayadd_1d(10_000, tile=1000)
    arrayadd.arrayadd_2d(50, 40, tile=(16, 16))
    arrayadd.arrayadd_1d(5_000, tile=500, mode=hc.RECURSIVE)
