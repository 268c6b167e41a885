"""Published chip peaks and the algorithmic work of the cost contraction.

Kept with the benchmark so that no change to the program can move the
yardstick a roofline share is measured against.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # bytes/s per chip


# Keyed by ``jax.devices()[0].device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" -- 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}

# flops per (k, l) term of the contraction, by ground loss:
# l2 = subtract, square, multiply by t, accumulate
FLOPS_PER_TERM = {"l2": 4}


def peaks(device_kind: str) -> Peaks:
    """Published peaks of one chip; a device not in the table is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


class Work(NamedTuple):
    flops: float
    bytes: float


def cost_contraction_work(s: int, m: int, n: int,
                          loss: str = "l2") -> Work:
    """Least work of one call of the COO cost contraction.

    One call computes, on a support of s sampled pairs (r_k, c_k),

        out_k = sum_l L(Cx[r_k, r_l], Cy[c_k, c_l]) * t_l + off_k,

    for k in [s]: s * s terms. With c_loss operations per term (l2:
    subtract, square, multiply by t, accumulate = 4):

        flops = c_loss * s**2.

    The least data any implementation must move is each input read once
    and the output written once, in float32 (4 bytes): the two relation
    matrices at the problem's own sizes m and n (not the bucket sizes the
    server pads to), m**2 + n**2 values, and the five support vectors
    rows, cols, t, off and out:

        bytes = 4 * (m**2 + n**2) + 4 * 5 * s.

    Both are lower bounds that depend on shapes alone, whatever
    implements the contraction (a gather-fused kernel, a materialized
    (s, s) loss matrix, an XLA gather), so a roofline share built on them
    can never exceed what is truly achievable.
    """
    if loss not in FLOPS_PER_TERM:
        raise ValueError(f"no operation count for loss {loss!r}")
    return Work(flops=float(FLOPS_PER_TERM[loss]) * s * s,
                bytes=4.0 * (m * m + n * n) + 4.0 * 5 * s)


def least_time_s(work: Work, pk: Peaks):
    """(seconds, binding bound): the larger of operations over peak
    FLOP/s and bytes over peak HBM bandwidth."""
    t_flops = work.flops / pk.flops
    t_bytes = work.bytes / pk.hbm_bw
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
