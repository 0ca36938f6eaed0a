"""``uts_roofline``: the least time the chip could take for one traversal
over the kernel time measured for one, in percent. The kernel is bound by
the VPU's 32-bit integer rate (no MXU work but the refill's gathers, no
HBM traffic but 6 MB of roots), so the least time is the operations of
the hashes no traversal can avoid over that rate (``peaks_vpu.json``).

The count is the configuration's ``hashed_nodes`` (the nodes at depths 1
to ``gen_mx - 1``, which ``check`` holds to the reference): a node at
depth ``gen_mx`` has no children whatever its state, so its state is never
needed. A kernel that hashes those too (as the engine does today) reads
lower for it, and one that stops doing so cannot read over 100 %.
"""

import json
import os

from ..reduce import device_time_per_count

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks_vpu.json")


def sha1_compression_ops() -> int:
    """32-bit operations of one SHA-1 compression of one block as FIPS
    180-1 section 7 writes it, nothing folded; a rotate is two shifts and
    an or. The 24-byte UTS message is one block."""
    rotl = 3
    schedule = 64 * (3 + rotl)  # W_t = S^1(four words xored), t = 16..79
    f = 20 * 4 + 20 * 2 + 20 * 5 + 20 * 2  # Ch, Parity, Maj, Parity
    # TEMP = S^5(A) + f + E + W_t + K_t, then C = S^30(B): per round
    rounds = 80 * (rotl + 4 + rotl) + f
    return schedule + rounds + 5  # and H_i += A..E


def least_seconds(cfg: dict, kind: str, peak: str) -> float:
    """The unavoidable hashes' operations over the peak of the device kind
    ``kind``; a kind the table lacks is an error, not a default."""
    with open(PEAKS) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise RuntimeError(f"device kind {kind!r} has no row in {PEAKS}")
    return cfg["hashed_nodes"] * sha1_compression_ops() / peaks[kind][peak]


def reduce(run, span: str, pattern: str, peak: str):
    kernel_s = device_time_per_count(run, span, pattern, "span", 1e-9)
    if kernel_s is None:  # no such span or no such kernel in the trace
        return None
    import jax

    least_s = least_seconds(run.cfg, jax.devices()[0].device_kind, peak)
    return 100.0 * least_s / kernel_s
