"""jit'd wrapper: VMEM-size gate + fallback to the jnp Sinkhorn."""
from __future__ import annotations

from typing import Optional

from repro.core.sinkhorn import sinkhorn as sinkhorn_jnp
from repro.kernels import dispatch
from repro.kernels.sinkhorn.sinkhorn import sinkhorn_pallas

# the kernel K stays resident in VMEM: the Pallas loop runs iff its
# float32 bytes fit, else the jnp Sinkhorn does
VMEM_BYTES = 8 * 2**20


def sinkhorn(a, b, K, iters: int = 50, interpret: Optional[bool] = None):
    m, n = K.shape
    if m * n * 4 <= VMEM_BYTES:
        return sinkhorn_pallas(a, b, K, iters=iters,
                               interpret=dispatch.interpret_mode(interpret))
    return sinkhorn_jnp(a, b, K, iters)
