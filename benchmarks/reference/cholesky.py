"""Plain reference for the tiled-Cholesky deployment: the matrix made
from the seed, the residual that decides ``correct``, and a blocked
factorisation at a stated matmul precision, which the control runs one
step below the configuration's float32. Plain ``jax.numpy``; imports
nothing of the program."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31
    )


@functools.partial(jax.jit, static_argnums=1)
def _spd(key, n: int):
    g = jax.random.normal(key, (n, n), jnp.float32)
    m = jnp.matmul(g, g.T, precision=HIGHEST)
    return (m + m.T) * 0.5 + n * jnp.eye(n, dtype=jnp.float32)


def make_spd(seed: int, n: int) -> np.ndarray:
    """A = sym(G G^T) + n I, G standard normal from the seed: made on
    the device in one jitted call, pulled to the host as float32 (the
    library call takes numpy in)."""
    return np.asarray(_spd(seed_key(seed), n))


@jax.jit
def _readings(L, a):
    m = jnp.matmul(L, L.T, precision=HIGHEST)
    return (
        jnp.max(jnp.abs(m - a)) / jnp.max(jnp.abs(a)),
        jnp.max(jnp.abs(jnp.triu(L, 1))),
        jnp.min(jnp.diagonal(L)),
        jnp.all(jnp.isfinite(L)),
    )


def readings(L: np.ndarray, a: np.ndarray) -> dict:
    """What the check compares: max|L L^T - A| / max|A| at HIGHEST, the
    largest entry above the diagonal, the smallest diagonal entry, and
    whether all of L is finite."""
    res, upper, diag, finite = _readings(jnp.asarray(L), jnp.asarray(a))
    return {
        "residual": float(res), "upper_max": float(upper),
        "diag_min": float(diag), "finite": bool(finite),
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _blocked(a, tile: int, precision: str):
    """Right-looking blocked Cholesky. Panels are factored and solved in
    float32; ``precision`` is that of the trailing update, where nearly
    all the operations are: 'float32' multiplies at HIGHEST, 'bfloat16'
    rounds both operands to bfloat16 (one MXU pass)."""
    n = a.shape[0]

    def mm(x, y):
        if precision == "bfloat16":
            return jnp.matmul(
                x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
        return jnp.matmul(x, y, precision=HIGHEST)

    for k in range(0, n, tile):
        e = k + tile
        with jax.default_matmul_precision("highest"):
            lkk = jnp.linalg.cholesky(a[k:e, k:e])
            a = a.at[k:e, k:e].set(lkk)
            if e < n:
                panel = jax.scipy.linalg.solve_triangular(
                    lkk, a[e:, k:e].T, lower=True
                ).T
        if e < n:
            a = a.at[e:, k:e].set(panel)
            a = a.at[e:, e:].add(-mm(panel, panel.T))
    return jnp.tril(a)


def blocked_cholesky(a: np.ndarray, tile: int, precision: str) -> np.ndarray:
    return np.asarray(_blocked(jnp.asarray(a), tile, precision))
