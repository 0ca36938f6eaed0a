"""Plain reference for the fib deployment: the value and the number of
descriptors the finish-async recursion makes, from arithmetic alone.
Imports nothing of the program."""


def fib(n: int) -> int:
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def descriptors(n: int) -> int:
    """Tasks the scalar tier executes for one root fib(n): one FIB task
    per call of the naive recursion (2 F(n+1) - 1) and one SUM join per
    call that recursed (F(n+1) - 1)."""
    return 3 * fib(n + 1) - 2
