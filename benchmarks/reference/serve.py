"""Plain reference for the tenant front door: what every request must
come back with, and what the resident graph's running sum must read.
Imports nothing of the program."""


def answer(x: int) -> int:
    return 3 * x + 1


def running_sum(args) -> int:
    """The resident sum is an int32 value slot: it wraps as int32 does."""
    s = sum(int(x) for x in args) & 0xFFFFFFFF
    return s - (1 << 32) if s >= (1 << 31) else s
