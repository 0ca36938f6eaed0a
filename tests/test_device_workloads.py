"""Device Cholesky (MXU tiles) and Smith-Waterman (VPU wavefront) tests."""

import jax
import numpy as np
import pytest

from hclib_tpu.device.cholesky import build_cholesky_graph, device_cholesky
from hclib_tpu.device.smithwaterman import device_sw
from hclib_tpu.models.cholesky import make_spd
from hclib_tpu.models.smithwaterman import random_seq, sw_seq

on_tpu = jax.default_backend() == "tpu"


def test_cholesky_graph_structure():
    # fused default: 4 potrf + 3 column TRSM streams + 6 row updates
    b = build_cholesky_graph(4)
    assert b.num_tasks == 4 + 3 + 6
    _, _, ring, counts = b.finalize(capacity=32, succ_capacity=128)
    assert counts[1] == 1  # only potrf(0) initially ready
    # tile-level TRSM (the reference's granularity): one task per tile
    b2 = build_cholesky_graph(4, fused_trsm=False)
    assert b2.num_tasks == 4 + 6 + 6
    _, _, _, counts2 = b2.finalize(capacity=32, succ_capacity=128)
    assert counts2[1] == 1


def test_device_cholesky_interpret():
    a = make_spd(256).astype(np.float32)
    L, info = device_cholesky(a, interpret=True)
    rel = np.max(np.abs(L @ L.T - a)) / np.max(np.abs(a))
    assert rel < 1e-5
    assert info["executed"] == 4


def test_device_cholesky_interpret_blocked_potrf():
    """tile=256 with factor_base=128 exercises the recursive 2x2 blocked
    factor_and_inv path (panel/update/inverse as block algebra) - the
    default base of min(tile, 256) would factor a 256 tile directly."""
    from hclib_tpu.device.cholesky import make_cholesky_megakernel

    a = make_spd(512).astype(np.float32)
    mk = make_cholesky_megakernel(2, interpret=True, tile=256,
                                  factor_base=128)
    L, info = device_cholesky(a, interpret=True, tile=256, mk=mk)
    rel = np.max(np.abs(L @ L.T - a)) / np.max(np.abs(a))
    assert rel < 1e-5
    assert info["executed"] == 4


def _strided_view(a32):
    wide = np.zeros((a32.shape[0], 2 * a32.shape[1]), np.float32)
    wide[:, ::2] = a32
    return wide[:, ::2]


# Every form rounds to the same C-contiguous float32 matrix.
INPUT_FORMS = {
    "float32": lambda a64: a64.astype(np.float32),
    "float64": lambda a64: a64,
    "fortran": lambda a64: np.asfortranarray(a64.astype(np.float32)),
    "view": lambda a64: _strided_view(a64.astype(np.float32)),
}


@pytest.fixture(scope="module", params=[(256, 128), (512, 256)],
                ids=["n256-t128", "n512-t256"])
def parent_path(request):
    """(a64, mk, tile, L) with L from the host-staged path device_cholesky
    took before its layout moved to the device: numpy tiles in, numpy
    tiles out, np.tril."""
    from hclib_tpu.device.cholesky import (
        _from_tiles, cholesky_buffers, make_cholesky_megakernel,
    )

    n, tile = request.param
    nt = n // tile
    a64 = make_spd(n, seed=n)
    mk = make_cholesky_megakernel(nt, interpret=True, tile=tile)
    a32 = np.ascontiguousarray(a64, np.float32)
    _, data, _ = mk.run(
        build_cholesky_graph(nt), data=cholesky_buffers(a32, nt, tile)
    )
    return a64, mk, tile, np.tril(_from_tiles(data["tiles"], nt, tile))


@pytest.mark.parametrize("form", INPUT_FORMS)
def test_device_cholesky_layout_on_device(parent_path, form):
    """The tile layout, the cast and the tril now run on the device: L is
    bit-identical to the host-staged path's whatever dtype and layout the
    caller's matrix has, and the caller's matrix is only read."""
    a64, mk, tile, L_parent = parent_path
    a = INPUT_FORMS[form](a64)
    before = a.copy()
    L, info = device_cholesky(a, mk=mk, tile=tile)
    assert np.array_equal(L, L_parent)
    assert L.dtype == np.float32 and L.flags.c_contiguous
    upper = L[np.triu_indices_from(L, 1)]
    assert not upper.any() and not np.signbit(upper).any()  # +0.0
    assert info["executed"] == 4  # nt = 2: 2 POTRF, 1 TRSMCOL, 1 UPDROW
    assert np.array_equal(a, before)


def test_device_cholesky_layout_compiles_once_per_shape(parent_path):
    """The two layout functions are jitted per shape, not per call or per
    Megakernel: a second call, and one through a fresh Megakernel of the
    same shape, add nothing to their caches."""
    from hclib_tpu.device import cholesky
    from hclib_tpu.device.cholesky import make_cholesky_megakernel

    a64, mk, tile, _ = parent_path
    a = a64.astype(np.float32)
    fns = (cholesky._tiles_on_device, cholesky._tril_on_device)
    device_cholesky(a, mk=mk, tile=tile)
    warm = [f._cache_size() for f in fns]
    assert min(warm) >= 1
    device_cholesky(a, mk=mk, tile=tile)
    mk2 = make_cholesky_megakernel(a.shape[0] // tile, interpret=True,
                                   tile=tile)
    device_cholesky(a, mk=mk2, tile=tile)
    assert [f._cache_size() for f in fns] == warm


def test_device_sw_interpret_multi_tile():
    a, b = random_seq(256, 3), random_seq(384, 4)
    score, h, info = device_sw(a, b, interpret=True)
    ref = sw_seq(a, b)[1:, 1:]
    assert np.array_equal(h, ref)
    assert score == int(ref.max())
    assert info["executed"] == 6


def test_device_sw_rejects_unaligned():
    with pytest.raises(ValueError):
        device_sw(random_seq(100, 1), random_seq(128, 2), interpret=True)


def test_device_sw_wave_interpret_exact():
    """The wave-batched SW engine (VERDICT r3 #4: the tile wavefront
    riding the vector tier - up to 8 anti-diagonal tiles as stacked VPU
    planes per task, wave chunks chained by real dependencies): exact
    against the sequential DP, and 'executed' counts tiles."""
    from hclib_tpu.device.smithwaterman import device_sw_wave

    a, b = random_seq(256, 3), random_seq(384, 4)
    score, h, info = device_sw_wave(a, b, interpret=True)
    ref = sw_seq(a, b)[1:, 1:]
    assert np.array_equal(h, ref)
    assert score == int(ref.max())
    assert info["executed"] == 6  # 2x3 tiles


@pytest.mark.skipif(not on_tpu, reason="needs TPU")
def test_device_sw_wave_tpu_matches_tile_engine():
    """On hardware, with anti-diagonals wider than one wave chunk (10x10
    tiles -> two chunks on the middle diagonals): the wave engine's full H
    matrix equals the tile-at-a-time engine's."""
    from hclib_tpu.device.smithwaterman import device_sw_wave

    a, b = random_seq(1280, 7), random_seq(1280, 8)
    score_t, h_t, info_t = device_sw(a, b, interpret=False)
    score_w, h_w, info_w = device_sw_wave(a, b, interpret=False)
    assert np.array_equal(h_w, h_t)
    assert score_w == score_t
    assert info_w["executed"] == info_t["executed"] == 100  # tiles


@pytest.mark.skipif(not on_tpu, reason="needs TPU")
def test_device_cholesky_tpu():
    a = make_spd(512).astype(np.float32)
    L, info = device_cholesky(a, interpret=False)
    rel = np.max(np.abs(L @ L.T - a)) / np.max(np.abs(a))
    assert rel < 1e-5, rel


@pytest.mark.skipif(not on_tpu, reason="needs TPU")
def test_device_cholesky_tpu_tile512():
    """The bench configuration's tile size: recursion depth 2 in
    factor_and_inv (512 -> 256 -> 128 base), residual checked on hardware
    (MXU precision differs from the interpret path)."""
    a = make_spd(1024).astype(np.float32)
    L, info = device_cholesky(a, interpret=False, tile=512)
    rel = np.max(np.abs(L @ L.T - a)) / np.max(np.abs(a))
    assert rel < 1e-5, rel
    assert info["executed"] == 4


@pytest.mark.skipif(not on_tpu, reason="needs TPU")
def test_device_sw_tpu():
    a, b = random_seq(256, 5), random_seq(256, 6)
    score, h, info = device_sw(a, b, interpret=False)
    ref = sw_seq(a, b)[1:, 1:]
    assert np.array_equal(h, ref)
