"""jit'd public wrapper for the SSD intra-chunk kernel."""
from __future__ import annotations

from typing import Optional

from repro.kernels import dispatch
from repro.kernels.ssd.ssd import ssd_intra_pallas

# heads per tile, capped at the head count
H_TILE = 8


def ssd_intra(xdt, cs, Bm, Cm, h_tile: Optional[int] = None,
              interpret: Optional[bool] = None):
    """xdt: (G, k, H, P), cs: (G, k, H), Bm/Cm: (G, k, N) -> (G, k, H, P)."""
    h_tile = min(h_tile or H_TILE, xdt.shape[2])
    return ssd_intra_pallas(xdt, cs, Bm, Cm, h_tile=h_tile,
                            interpret=dispatch.interpret_mode(interpret))
