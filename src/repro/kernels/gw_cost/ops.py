"""jit'd public wrapper for the gw_cost kernel: padding and its block."""
from __future__ import annotations

from typing import Optional

from repro.kernels import dispatch
from repro.kernels.gw_cost.gw_cost import gw_cost_pallas

# every axis of the 4-D contraction is tiled in blocks of BLOCK
BLOCK = 32


def gw_cost(A, B, T, loss: str = "l1", block: Optional[int] = None,
            interpret: Optional[bool] = None):
    """C[k,m] = Σ_{l,p} L(A[k,l], B[m,p]) T[l,p], padded + unpadded."""
    K, M = A.shape[0], B.shape[0]
    block = block or BLOCK
    A_p, _ = dispatch.pad_to_multiple(A, (block, block))
    B_p, _ = dispatch.pad_to_multiple(B, (block, block))
    T_p, _ = dispatch.pad_to_multiple(T, (block, block))
    # zero-padded T rows/cols contribute L(A,B)*0 = 0; padded A/B rows only
    # produce extra output rows/cols, sliced away below.
    out = gw_cost_pallas(A_p, B_p, T_p, loss=loss, bk=block, bm=block,
                         bl=block, bp=block,
                         interpret=dispatch.interpret_mode(interpret))
    return out[:K, :M]
