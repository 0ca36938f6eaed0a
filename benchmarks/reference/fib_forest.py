"""Plain reference for the fib-forest deployments: the value and the number
of descriptors of a forest of ``roots`` x fib(``n``), by the arithmetic of
the naive finish-async recursion, and the same two numbers for one root
counted by running that recursion. Imports nothing of the program, and
nothing of ``reference/fib.py`` either: the closed form is written out
here, so that ``check`` can hold it to the direct count."""


def fib(n: int) -> int:
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def closed_form(roots: int, n: int) -> dict:
    """One FIB descriptor per call of the recursion (2 F(n+1) - 1 a root)
    and one SUM join per call that recursed (F(n+1) - 1 a root)."""
    return {"value": roots * fib(n),
            "descriptors": roots * (3 * fib(n + 1) - 2)}


def direct_count(n: int) -> dict:
    """Value and descriptors of ONE root, by running the recursion the
    program runs: fib(n) spawns fib(n-1) and fib(n-2) and one join that
    adds them; fib(0) and fib(1) are leaves. An explicit stack, so that a
    deep n is no recursion limit (fib(18) is 8,361 calls)."""
    value = descriptors = 0
    stack = [n]
    while stack:
        k = stack.pop()
        descriptors += 1  # the FIB task of this call
        if k < 2:
            value += k
        else:
            descriptors += 1  # its SUM join
            stack += [k - 1, k - 2]
    return {"value": value, "descriptors": descriptors}
