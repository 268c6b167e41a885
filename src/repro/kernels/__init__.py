"""Pallas TPU kernels (validated in interpret mode on CPU; Mosaic on TPU):

  spar_cost/        COO cost assembly on the sampled support — the served
                    SPAR-GW hot path (fused gather kernel, materialized
                    matvec)
  gw_cost/          grid GW cost assembly — the paper's O(s^2) hotspot
  flash_attention/  causal GQA online-softmax attention
  sinkhorn/         VMEM-resident Sinkhorn scaling loop
  ssd/              Mamba2 SSD intra-chunk (masked-decay) block

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper and the family's sizes), ref.py (pure-jnp oracle); sweeps in
tests/test_kernels.py and tests/test_spar_cost_kernel.py. dispatch.py
resolves the backend and interpret mode and holds the padding helpers.
"""
