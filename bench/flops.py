"""Operation and byte counts of the NTTD decode and fit, from the widths alone.

Model FLOPs per decoded entry count the multiply-adds of the dense
contractions (2 per multiply-add): the LSTM's two gate products at every
one of the d' steps, the first, middle and last TT-core heads, and the
R-wide chain.  The one-hot embedding gather of the decode kernel is not
counted (it is an implementation of a row lookup, not model work), nor
are the elementwise gate nonlinearities.
"""
from __future__ import annotations


def decode_flops_per_entry(d_prime: int, hidden: int, rank: int) -> int:
    """Forward model FLOPs of one decoded entry."""
    if d_prime < 2:
        raise ValueError(f"NTTD needs d' >= 2, got {d_prime}")
    h, r = hidden, rank
    lstm = d_prime * 2 * (2 * h * 4 * h)      # x @ wi and h @ wh per step
    heads = 2 * (2 * h * r)                   # first and last core heads
    mids = (d_prime - 2) * (2 * h * r * r)    # middle core heads
    chain = (d_prime - 2) * (2 * r * r) + 2 * r  # row vector x R x R, final dot
    return lstm + heads + mids + chain


def fit_flops_per_entry(d_prime: int, hidden: int, rank: int) -> int:
    """Forward plus backward FLOPs of one trained entry (3x the forward)."""
    return 3 * decode_flops_per_entry(d_prime, hidden, rank)


def decode_bytes_per_entry(d_prime: int, dtype_bytes: int = 4) -> int:
    """HBM bytes a decoded entry moves: its d' int32 folded indices in and
    one value out.  The weights are read once per call and not counted."""
    return 4 * d_prime + dtype_bytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for the work, and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
