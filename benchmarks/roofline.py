"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell, with the peaks of the record's device:
  compute term    = FLOPs_per_device / peak_FLOP/s
  memory term     = bytes_per_device / HBM_bw
  collective term = wire_bytes_per_device / ICI_bw        (per link;
                    HLO is the per-device program, so per-device wire bytes
                    over per-chip link bw == global_bytes/(chips·link_bw))
plus MODEL_FLOPS = 6·N·D (train) / 2·N·D (fwd) vs compiled FLOPs.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per chip-to-chip link


# keyed by ``jax.devices()[0].device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e) — 197 TFLOP/s
# bf16, 819 GB/s HBM, 1,600 Gbit/s interchip interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """Published peaks of one chip; a device not in the table is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

ART = Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
AUTOTUNE_ART = Path(__file__).resolve().parents[1] / "artifacts" / "autotune"

SHAPE_TOKENS = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
                "decode_32k": 128, "long_500k": 1}


def model_flops(rec) -> float:
    """6·N·D for train, 2·N·D forward-only (decode: D = batch tokens)."""
    if rec["kind"] == "gw" or rec["shape"] not in SHAPE_TOKENS:
        return 0.0
    n = rec["n_params"]
    toks = SHAPE_TOKENS[rec["shape"]]
    mult = 6.0 if rec["kind"] == "train" else 2.0
    # MoE: active params only
    arch = rec["arch"]
    active_frac = 1.0
    if "llama4-scout" in arch:
        active_frac = (1 + 2) / 17.0 * 1.7      # ~2 of 17B active (top1+shared)
    if "phi3.5-moe" in arch:
        active_frac = 6.6 / 42.0
    return mult * n * active_frac * toks


def load_cells(mesh: str = None, tag: str = ""):
    cells = []
    for p in sorted(ART.glob("*.json")):
        with open(p) as f:
            rec = json.load(f)
        if mesh and rec.get("mesh") != mesh:
            continue
        if rec.get("tag", "") != tag:
            continue
        cells.append(rec)
    return cells


def analyze(rec):
    chips = 1
    for v in rec["mesh_shape"].values():
        chips *= v
    pk = peaks(rec.get("device_kind", "unrecorded"))
    t_comp = rec["flops_per_device"] / pk.flops
    t_mem = rec["bytes_per_device"] / pk.hbm_bw
    wire = sum(v["wire_bytes"] for v in rec["collectives"].values())
    t_coll = wire / pk.ici_bw
    dom = max((("compute", t_comp), ("memory", t_mem),
               ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(rec)
    hlo_global = rec["flops_per_device"] * chips
    ratio = mf / hlo_global if hlo_global else 0.0
    bound = max(t_comp, t_mem, t_coll)
    frac = t_comp / bound if bound else 0.0   # roofline fraction (compute/limit)
    return {"arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "t_compute_s": t_comp, "t_memory_s": t_mem,
            "t_collective_s": t_coll, "dominant": dom,
            "model_flops": mf, "hlo_flops_global": hlo_global,
            "useful_ratio": ratio, "roofline_fraction": frac,
            "temp_GiB": rec["memory"]["temp_bytes"] / 2**30,
            "args_GiB": rec["memory"]["argument_bytes"] / 2**30}


def table(mesh="single", tag=""):
    rows = [analyze(r) for r in load_cells(mesh, tag)]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    return rows


def autotune_table():
    """Kernel micro-autotune records (written via repro.kernels.dispatch
    by the benchmarks, e.g. bench_spar_cost). One row per sweep."""
    rows = []
    for p in sorted(AUTOTUNE_ART.glob("*.json")) if AUTOTUNE_ART.exists() \
            else []:
        with open(p) as f:
            rows.extend(json.load(f))
    return rows


def main():
    tune = autotune_table()
    if tune:
        print("\n=== kernel autotune (dispatch records) ===")
        print(f"{'family':18s} {'backend':8s} {'best':>6s}  timings")
        for r in tune:
            timings = " ".join(f"{k}:{v*1e6:.0f}us"
                               for k, v in sorted(r["timings_s"].items(),
                                                  key=lambda kv: int(kv[0])))
            print(f"{r['family']:18s} {r['backend']:8s} "
                  f"{r['best_block']:6d}  {timings}")
    for mesh in ("single", "multi"):
        rows = table(mesh)
        if not rows:
            continue
        print(f"\n=== mesh: {mesh} ===")
        hdr = (f"{'arch':26s} {'shape':12s} {'comp(s)':>9s} {'mem(s)':>9s} "
               f"{'coll(s)':>9s} {'dominant':>10s} {'6ND/HLO':>8s} "
               f"{'frac':>6s} {'temp':>7s}")
        print(hdr)
        for r in rows:
            print(f"{r['arch']:26s} {r['shape']:12s} "
                  f"{r['t_compute_s']:9.2e} {r['t_memory_s']:9.2e} "
                  f"{r['t_collective_s']:9.2e} {r['dominant']:>10s} "
                  f"{r['useful_ratio']:8.2f} {r['roofline_fraction']:6.2f} "
                  f"{r['temp_GiB']:6.1f}G")


if __name__ == "__main__":
    main()
