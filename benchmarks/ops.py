"""Operations and bytes an algorithm needs, from its configuration's
sizes alone: the numerators of every roofline share. Kept with the
benchmark so that no PR that claims a gain can change them."""


def cholesky_flops(cfg: dict) -> float:
    """n^3/3: the floating-point operations of a dense Cholesky
    factorisation, in the precision the caller asked for (float32). The
    program spends three bf16 MXU passes on each f32-accurate product, so
    a third of the bf16 peak is this algorithm's ceiling on the chip."""
    return cfg["n"] ** 3 / 3.0
