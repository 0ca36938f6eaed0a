"""Reusable device op kernels: the MXU/VPU building blocks task kernels
compose (the layer the package docstring calls ``hclib_tpu.ops``).

- ``tiles``: MXU tile linear algebra (transpose-free A@B^T contraction,
  masked rank-1 Cholesky factorization, Newton-Schulz triangular inverse)
  and the DMA start/wait helper used by megakernel task kernels.
- ``sha1``: the FIPS-180-1 compression function vectorized over arrays of
  any shape, generic over numpy (the seeding's small levels on the host)
  and jnp (its larger levels and the engines' planes on the device) - the
  UTS splittable RNG.
- ``scan``: decay-cummax, the log-depth solution of recurrences
  c[j] = max(t[j], c[j-1] - g) used by the Smith-Waterman row sweep.
"""

from .scan import decay_cummax  # noqa: F401
from .sha1 import sha1_block, sha1_child  # noqa: F401
from .tiles import (  # noqa: F401
    dma_copy,
    factor_and_inv,
    factor_tile,
    mm_nt,
    tri_inverse,
)

__all__ = [
    "decay_cummax",
    "sha1_block",
    "sha1_child",
    "dma_copy",
    "factor_and_inv",
    "factor_tile",
    "mm_nt",
    "tri_inverse",
]
