"""The deployment ``sw-wave`` (benchmark cell ``sw-wave-8192``) at small
sizes: ``device_sw_wave`` through the Pallas interpreter, held to the
benchmark's plain reference on the best score, H's last row and H's last
column (``info["last_row"]`` / ``info["last_col"]``, the boundary buffers
``Megakernel.run`` brings back), the reference to its own cell-by-cell
recurrence, and the four ``bench:sw.*`` spans of a call. The kernel at the
cell's size is asked of the real compiler in tests/test_chip_compile.py.

One ``Megakernel`` a shape: a square 512 x 512 (4 x 4 tiles, 7 waves) and
a rectangular 384 x 640 (3 x 5 tiles, 7 waves) with the matrix kept; the
other half of each (the square with the matrix, the rectangle without) and
pairs one tile high and one tile wide build their own (PR 54: the sweep's
row takes its columns from the row before it, in the loop's carry)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import sw as ref  # noqa: E402
from hclib_tpu.device import smithwaterman as sw  # noqa: E402
from hclib_tpu.runtime import spans  # noqa: E402

SEEDS = [35, 2**31 + 35]
SPANS = ["bench:sw.build", "bench:sw.stage", "bench:sw.run",
         "bench:sw.readback"]


@pytest.fixture(scope="module")
def mk_square():
    return sw.make_sw_wave_megakernel(4, 4, interpret=True, with_h=False)


@pytest.fixture(scope="module")
def mk_rect():
    return sw.make_sw_wave_megakernel(3, 5, interpret=True, with_h=True)


def held_to_reference(a, b, score, info):
    want = ref.sw_last(a, b)
    assert score == want["score"] > 0
    assert info["last_row"].shape == (len(b),)
    assert info["last_col"].shape == (len(a),)
    assert np.array_equal(info["last_row"], want["last_row"])
    assert np.array_equal(info["last_col"], want["last_col"])
    assert info["pending"] == 0 and not info["overflow"]
    assert info["tiers"]["scalar_tasks"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_square_pair_equals_the_plain_reference(mk_square, seed):
    a, b = ref.make_pair(seed, 512, 512)
    score, h, info = sw.device_sw_wave(
        a, b, interpret=True, mk=mk_square, with_h=False)
    assert h is None
    held_to_reference(a, b, score, info)
    counts = ref.wave_counts(4, 4, sw.WAVE_R)
    assert info["executed"] == counts["tiles"] == 16
    assert info["tiers"]["batch_tasks"] == counts["descriptors"] == 7


@pytest.mark.parametrize("seed", SEEDS)
def test_rectangular_pair_equals_the_plain_reference(mk_rect, seed):
    a, b = ref.make_pair(seed, 384, 640)
    score, h, info = sw.device_sw_wave(
        a, b, interpret=True, mk=mk_rect, with_h=True)
    held_to_reference(a, b, score, info)
    assert info["executed"] == 15
    # with the matrix kept, the two vectors are its last row and column
    assert h.shape == (384, 640) and score == h.max()
    assert np.array_equal(info["last_row"], h[-1, :])
    assert np.array_equal(info["last_col"], h[:, -1])
    assert np.array_equal(h, ref.sw_naive(a, b))


SHAPES = {  # (n, m): tiles high x tiles wide
    "square": (512, 512),
    "rect": (384, 640),
    "one_tile_high": (128, 384),  # every tile's row 0 meets no tile above
    "one_tile_wide": (384, 128),  # every tile's left boundary is the zero one
}
OTHER_HALF = [("square", True), ("rect", False)] + [
    (shape, with_h) for shape in ("one_tile_high", "one_tile_wide")
    for with_h in (False, True)]


@pytest.mark.parametrize("shape,with_h", OTHER_HALF)
def test_pair_equals_the_plain_reference_with_and_without_h(shape, with_h):
    n, m = SHAPES[shape]
    a, b = ref.make_pair(SEEDS[1] + n, n, m)
    score, h, info = sw.device_sw_wave(a, b, interpret=True, with_h=with_h)
    held_to_reference(a, b, score, info)
    assert info["executed"] == (n // sw.T) * (m // sw.T)
    if with_h:
        assert np.array_equal(h, ref.sw_naive(a, b))
    else:
        assert h is None


def test_the_four_spans_are_entered_once_a_call(mk_square, monkeypatch):
    opened = []

    class Span:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Span)
    a, b = ref.make_pair(SEEDS[1], 512, 512)
    for _ in range(2):
        sw.device_sw_wave(a, b, interpret=True, mk=mk_square, with_h=False)
    # the build ledger's two (the first call builds; a jnp pass may
    # compile) are told apart in tests/test_progcache.py
    opened = [n for n in opened if not n.startswith("bench:prog.")]
    assert [n for n in opened if n.startswith("bench:sw.")] == SPANS * 2
    # Megakernel.run's own four nest inside bench:sw.run
    at = opened.index("bench:sw.run")
    assert opened[at + 1:at + 5] == [
        "bench:mk.finalize", "bench:mk.upload", "bench:mk.launch",
        "bench:mk.wait"]
    assert len(opened) == 2 * (len(SPANS) + 4)


PAIRS = {
    "random": lambda: ref.make_pair(35, 97, 61),
    "random_wide": lambda: ref.make_pair(36, 40, 130),
    "all_matches": lambda: (np.full(48, 3, np.int32),
                            np.full(64, 3, np.int32)),
    "no_match": lambda: (np.zeros(48, np.int32), np.ones(64, np.int32)),
    "one_row": lambda: ref.make_pair(37, 1, 50),
    "one_column": lambda: ref.make_pair(38, 50, 1),
}


@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_reference_row_sweep_equals_the_naive_recurrence(pair):
    a, b = PAIRS[pair]()
    h, r = ref.sw_naive(a, b), ref.sw_last(a, b)
    assert r["score"] == h.max()
    assert np.array_equal(r["last_row"], h[-1, :])
    assert np.array_equal(r["last_col"], h[:, -1])
    if pair == "all_matches":
        assert r["score"] == 2 * 48
    if pair == "no_match":
        assert r["score"] == 0 and not h.any()


def test_reference_takes_the_configurations_scoring():
    a, b = ref.make_pair(39, 60, 70)
    kw = {"match": 3, "mismatch": -2, "gap": 2}
    h, r = ref.sw_naive(a, b, **kw), ref.sw_last(a, b, **kw)
    assert r["score"] == h.max() != ref.sw_last(a, b)["score"]
    assert np.array_equal(r["last_row"], h[-1, :])
    assert np.array_equal(r["last_col"], h[:, -1])
