"""Vectorized UTS tests (CPU backend; exactness vs the sequential spec)."""

import jax
import pytest

from hclib_tpu.device.uts_vec import (
    child_threshold_table,
    child_thresholds,
    depth_cap,
    uts_vec,
)
from hclib_tpu.models.uts import (
    CYCLIC,
    EXPDEC,
    FIXED,
    LINEAR,
    T_TINY,
    UTSParams,
    count_seq,
    num_children,
)


def _cpu():
    return jax.devices("cpu")[0]


def test_thresholds_exact_against_scalar_formula():
    """count(r) = #{k: r >= t_k} must reproduce num_children for many r."""
    b0 = 4.0
    ts = child_thresholds(b0)
    params = UTSParams(shape=FIXED, gen_mx=100, b0=b0, root_seed=1)
    import struct

    for r in [0, 1, 429496729, 1073741824, 1717986918, 2147483646,
              2147483647, 214748364, 2100000000]:
        state = b"\x00" * 16 + struct.pack(">I", r)
        want = num_children(params, state, 1)
        got = int((r >= ts).sum())
        assert got == want, (r, got, want)


def test_uts_vec_t3_exact():
    r = uts_vec(T_TINY, target_roots=64, device=_cpu(), stack_pad=8)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == count_seq(T_TINY)


def test_uts_vec_deeper_tree_exact():
    p = UTSParams(shape=FIXED, gen_mx=7, b0=4.0, root_seed=19)
    r = uts_vec(p, target_roots=256, device=_cpu(), stack_pad=8)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == count_seq(p)


def test_uts_vec_tiny_tree_host_only():
    """A tree smaller than target_roots is fully consumed by the host BFS."""
    p = UTSParams(shape=FIXED, gen_mx=2, b0=1.0, root_seed=3)
    r = uts_vec(p, target_roots=10_000, device=_cpu())
    assert (r["nodes"], r["leaves"], r["max_depth"]) == count_seq(p)


def test_threshold_table_matches_scalar_formula_per_depth():
    """Every table row must reproduce num_children at its depth (the f64
    shape functions, reference test/uts/uts.c:171-221)."""
    import struct

    for shape in (LINEAR, EXPDEC, CYCLIC):
        p = UTSParams(shape=shape, gen_mx=6, b0=4.0, root_seed=1)
        cap = depth_cap(p) or 30
        tab = child_threshold_table(p, cap)
        for d in [0, 1, 2, 5, cap // 2, cap]:
            row = tab[d]
            for r in [0, 1, 1073741824, 1717986918, 2147483646, 2147483647]:
                state = b"\x00" * 16 + struct.pack(">I", r)
                want = num_children(p, state, d)
                got = int(((row >= 0) & (r >= row)).sum())
                assert got == want, (shape, d, r, got, want)


@pytest.mark.parametrize(
    "shape,gen_mx,b0,seed",
    [
        (LINEAR, 8, 4.0, 34),
        (CYCLIC, 1, 6.0, 502),
        (EXPDEC, 3, 3.0, 502),
    ],
)
def test_uts_vec_depth_varying_shapes_exact(shape, gen_mx, b0, seed):
    """LINEAR/EXPDEC/CYCLIC trees count exactly vs the sequential spec
    (VERDICT r1 item 6; reference trees T5/T2 are these shapes at scale).
    Shallow parameterizations on purpose: compile time grows steeply with
    the per-lane stack height (= depth cap), and the CYCLIC gen_mx=1 tree
    still spans the full period of its threshold table."""
    p = UTSParams(shape=shape, gen_mx=gen_mx, b0=b0, root_seed=seed)
    # A tight EXPDEC bound keeps the per-lane stack (and with it compile
    # time) small; the engine raises if the tree ever reaches it.
    kw = {"depth_bound": 9} if shape == EXPDEC else {}
    # stack_pad + table_cols land every parameterization on ONE
    # padded-shape engine (one XLA compile for the whole matrix).
    r = uts_vec(p, target_roots=128, device=_cpu(), stack_pad=10,
                table_cols=100, **kw)
    assert (r["nodes"], r["leaves"], r["max_depth"]) == count_seq(p)


def test_uts_vec_expdec_depth_bound_raises():
    """An EXPDEC tree that reaches the configured depth bound must fail
    loudly, never silently truncate."""
    p = UTSParams(shape=EXPDEC, gen_mx=3, b0=3.0, root_seed=502)
    _, _, true_maxd = count_seq(p)
    # target_roots small enough that the engine (not the host BFS) does
    # the deep traversal - a large target consumes this 217-node tree on
    # the host and nothing ever reaches the bound.
    with pytest.raises(RuntimeError, match="depth bound"):
        uts_vec(p, target_roots=8, device=_cpu(), stack_pad=10,
                table_cols=100, depth_bound=max(2, true_maxd - 2))
