"""jit'd public wrapper: (B,S,H,hd)/(B,S,K,hd) layout + GQA flattening."""
from __future__ import annotations

from typing import Optional

from repro.kernels import dispatch
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas

# query and key blocks, each capped at the sequence length
BLOCK = 128


def flash_attention(q, k, v, causal: bool = True, bq: Optional[int] = None,
                    bk: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, S, H, hd); k, v: (B, S, K, hd). Causal GQA attention."""
    assert causal, "kernel implements the causal (LM) case"
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    bq = min(bq or BLOCK, S)
    bk = min(bk or BLOCK, S)
    # (B, S, H, hd) -> (B*H, S, hd) with head-major flattening so that
    # q head b*H + h maps to kv head (b*H + h)//G == b*K + h//G.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    out = flash_attention_pallas(qf, kf, vf, groups=G, bq=bq, bk=bk,
                                 interpret=dispatch.interpret_mode(interpret))
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
