"""``slu_roofline``: the least time the chip could take for one SparseLU
call over the kernel time measured for one, in percent.

The count is the algorithm's, from the reference's symbolic factorisation
of the configuration's pattern (``reference/sparselu.py``: ``2 m^3`` a
``bmod``, ``m^3`` a ``fwd`` or a ``bdiv``, ``2/3 m^3`` a ``lu0``; 0.750
TFLOP at ``n`` 128, ``m`` 128), in the precision the configuration states,
over the bf16 peak (``peaks.json``): it reads the same work whatever
implements it. A program that spends three bf16 MXU passes on each
f32-accurate product cannot pass a third of that peak, so 33 % is the
ceiling, as ``chol_roofline``'s. The explicit inverses the program forms
in ``lu0`` and its ranges' tests are not counted, so the share cannot
pass 100 %.
"""

import functools

from ..reduce import device_time_per_count
from ..reference import sparselu as ref


@functools.lru_cache(maxsize=None)
def least_flops(n: int, m: int) -> float:
    return ref.flops(ref.symbolic(ref.genmat_pattern(n))["counts"], m)


def reduce(run, span: str, pattern: str, peak: str):
    kernel_s = device_time_per_count(run, span, pattern, "span", 1e-9)
    if kernel_s is None:  # no such span or no such kernel in the trace
        return None
    least = least_flops(run.cfg["n"], run.cfg["m"])
    return 100.0 * least / run.peaks[peak] / kernel_s
