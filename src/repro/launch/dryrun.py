import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: SPMD
partitioning must succeed, memory_analysis must fit, and the compiled HLO
yields the roofline terms (FLOPs / bytes / collective bytes) recorded to
``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import base as cb
from repro.distrib import sharding as shd
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models.attention import set_flash_chunk
from repro.models.model_zoo import Model, set_activation_sharding
from repro.optim import adamw

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _tensor_bytes(dtype: str, dims: str) -> int:
    nelem = 1
    if dims:
        for d in dims.split(","):
            nelem *= int(d)
    return nelem * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str):
    """Sum output bytes + ring-model wire bytes per collective op kind."""
    out = {k: {"count": 0, "out_bytes": 0, "wire_bytes": 0.0}
           for k in _COLL_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in _COLL_OPS:
            token = f" {op}("
            alt = f" {op}-start("
            pos = stripped.find(token)
            if pos < 0:
                pos = stripped.find(alt)
            if pos < 0 or " = " not in stripped[:pos + 4]:
                continue
            lhs = stripped.split(f"{op}(")[0].split(f"{op}-start(")[0]
            sizes = [_tensor_bytes(d, s) for d, s in _SHAPE_RE.findall(lhs)]
            ob = sum(sizes)
            m = _GROUP_RE.search(stripped)
            if m:
                g = len(m.group(1).split(","))
            else:
                m2 = _GROUP_RE2.search(stripped)
                g = int(m2.group(2)) if m2 else 2
            if g <= 1:
                continue            # degenerate single-device group: no wire
            if op == "all-gather":
                wire = ob * (g - 1) / g
            elif op == "all-reduce":
                wire = ob * 2 * (g - 1) / g
            elif op == "reduce-scatter":
                wire = ob * (g - 1)
            elif op == "all-to-all":
                wire = ob * (g - 1) / g
            else:  # collective-permute
                wire = ob
            out[op]["count"] += 1
            out[op]["out_bytes"] += ob
            out[op]["wire_bytes"] += wire
            break
    return out


def _metrics_shardings(mesh):
    rep = shd.replicated(mesh)
    return {"loss": rep, "ce": rep, "aux": rep, "gnorm": rep, "lr": rep}


def _batch_shardings(mesh, batch_specs, global_batch, seq_len):
    out = {}
    for k, v in batch_specs.items():
        if k == "cache":
            out[k] = jax.tree.map(
                lambda s: _cache_sharding(mesh, s.shape, global_batch, seq_len),
                v)
        elif k == "index":
            out[k] = shd.replicated(mesh)
        else:
            out[k] = shd.batch_sharding(mesh, len(v.shape), global_batch)
    return out


def _cache_sharding(mesh, shape, batch, seq_len):
    """Caches: stacked (L, B, S, ...) or unstacked (B, S, ...) or states
    (L, B, ...). Batch -> data axes; seq dim -> 'model' (plus data axes when
    batch is unshardable, e.g. the long-context B=1 cells)."""
    dp = shd.data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    model_size = mesh.shape["model"]
    spec = [None] * len(shape)
    # locate batch dim: index 1 if stacked else 0
    bdim = None
    for cand in (1, 0):
        if len(shape) > cand and shape[cand] == batch:
            bdim = cand
            break
    if bdim is not None and batch % dp_size == 0 and batch > 1:
        spec[bdim] = dp
        sdim = bdim + 1
        if len(shape) > sdim and shape[sdim] == seq_len \
                and seq_len % model_size == 0:
            spec[sdim] = "model"
    elif bdim is not None:
        sdim = bdim + 1
        if len(shape) > sdim and shape[sdim] == seq_len:
            if seq_len % (dp_size * model_size) == 0:
                spec[sdim] = tuple(dp) + ("model",)
            elif seq_len % model_size == 0:
                spec[sdim] = "model"
    return NamedSharding(mesh, P(*spec))


def _build_fn(cfg, shape, mesh, use_flash, rules, unroll: bool = False):
    """Construct the jitted step fn + abstract args for one cell."""
    model = Model(cfg, unroll_layers=unroll)
    abstract = model.abstract_params()
    axes = model.param_axes()
    param_sh = shd.param_shardings(axes, abstract, mesh, rules)
    batch_specs = sp.input_specs(cfg, shape)
    batch_sh = _batch_shardings(mesh, batch_specs, shape.global_batch,
                                shape.seq_len)
    if shape.kind == "train":
        opt_abs = adamw.abstract_state(abstract)
        opt_sh = adamw.AdamWState((shd.replicated(mesh)), param_sh, param_sh)
        step = make_train_step(model, use_flash=use_flash)
        fn = jax.jit(step,
                     in_shardings=(param_sh, opt_sh, batch_sh),
                     out_shardings=(param_sh, opt_sh, _metrics_shardings(mesh)))
        args = (abstract, opt_abs, batch_specs)
    elif shape.kind == "prefill":
        step = make_prefill_step(model, use_flash=use_flash)
        fn = jax.jit(step, in_shardings=(param_sh, batch_sh))
        args = (abstract, batch_specs)
    else:
        step = make_decode_step(model)
        fn = jax.jit(step, in_shardings=(param_sh, batch_sh))
        args = (abstract, batch_specs)
    return fn, args, abstract


def _slstm_correction_flops(cfg, shape):
    """Per-device FLOPs missed because sLSTM's seq scan is counted once by
    cost_analysis: (S-1) extra steps x 4 recurrent per-head matmuls."""
    n_slstm = (list(cfg.block_pattern).count("slstm")
               * cfg.resolved_superblocks
               + list(cfg.tail_blocks).count("slstm"))
    if n_slstm == 0:
        return 0.0
    pd = int(cfg.lstm_proj_factor * cfg.d_model)
    hd = pd // cfg.n_heads
    S = shape.seq_len if shape.kind in ("train", "prefill") else 1
    per_step = 2 * 4 * pd * hd * shape.global_batch
    fwd = n_slstm * (S - 1) * per_step
    mult = 3.0 if shape.kind == "train" else 1.0   # fwd+bwd
    return fwd * mult


def cost_extrapolate(cfg, shape, mesh, use_flash, rules,
                     flash_chunk: int = 1 << 30):
    """cost_analysis counts scan bodies once -> compile L=1 and L=2
    *unrolled* superblock variants and extrapolate flops/bytes linearly in
    the superblock count.

    flash_chunk = huge  -> single attention chunk: exact FLOP count, but
                           bytes include the S^2 score materialization the
                           production flash path avoids (upper bound).
    flash_chunk = 512   -> production blockwise program: bytes approximate
                           fused/VMEM-resident HBM traffic (chunk transients
                           counted once — the on-chip ideal); attention
                           FLOPs undercounted (use the other variant).
    """
    set_flash_chunk(flash_chunk)
    vals = {}
    for L in (1, 2):
        cfg_l = dataclasses.replace(cfg, n_superblocks=L)
        fn, args, _ = _build_fn(cfg_l, shape, mesh, use_flash, rules,
                                unroll=True)
        with mesh:
            c = fn.lower(*args).compile()
        ca = c.cost_analysis()
        vals[L] = (ca.get("flops", 0.0), ca.get("bytes accessed", 0.0))
    set_flash_chunk(512)
    n_sb = cfg.resolved_superblocks
    flops = vals[1][0] + (n_sb - 1) * (vals[2][0] - vals[1][0])
    byts = vals[1][1] + (n_sb - 1) * (vals[2][1] - vals[1][1])
    chips = int(np.prod(list(mesh.shape.values())))
    flops += _slstm_correction_flops(cfg, shape) / chips
    return flops, byts, {str(k): v for k, v in vals.items()}


def _add_cost_fields(rec, cfg, shape, mesh, use_flash, rules):
    """Scan-aware FLOP/byte accounting (two unrolled variants)."""
    flops, byts, pts = cost_extrapolate(cfg, shape, mesh, use_flash, rules)
    rec["flops_per_device"] = flops
    rec["bytes_unblocked_per_device"] = byts
    rec["cost_points"] = pts
    if shape.kind != "decode":
        _, byts_f, pts_f = cost_extrapolate(cfg, shape, mesh, use_flash,
                                            rules, flash_chunk=512)
        rec["bytes_per_device"] = byts_f
        rec["cost_points_flash"] = pts_f
    else:
        rec["bytes_per_device"] = byts
    return rec


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             use_flash: bool = True, rules=None, tag: str = "",
             sp: bool = False, with_cost: bool = True, cfg_overrides=None):
    cfg = cb.get_arch(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = cb.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    dp = shd.data_axes(mesh)
    set_activation_sharding(
        True, dp=dp,
        dp_size=int(np.prod([mesh.shape[a] for a in dp])),
        model_size=mesh.shape["model"], sp=sp)
    model = Model(cfg)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mesh_shape": dict(mesh.shape), "kind": shape.kind, "tag": tag,
           "device_kind": mesh.devices.flat[0].device_kind}
    t0 = time.time()

    fn, args, abstract = _build_fn(cfg, shape, mesh, use_flash, rules)
    rec["n_params"] = sum(int(np.prod(s.shape))
                          for s in jax.tree.leaves(abstract))

    with mesh:
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec["lower_s"] = round(t1 - t0, 2)
    rec["compile_s"] = round(t2 - t1, 2)
    rec["memory"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }
    rec["flops_raw"] = cost.get("flops", 0.0)
    rec["bytes_raw"] = cost.get("bytes accessed", 0.0)
    hlo = compiled.as_text()
    rec["collectives"] = parse_collectives(hlo)
    rec["hlo_lines"] = hlo.count("\n")
    del compiled, lowered, hlo

    if with_cost:
        _add_cost_fields(rec, cfg, shape, mesh, use_flash, rules)
    else:
        rec["flops_per_device"] = rec["flops_raw"]
        rec["bytes_per_device"] = rec["bytes_raw"]
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch.replace('/','_')}__{shape_name}__{mesh_kind}"
    if tag:
        fname += f"__{tag}"
    with open(out_dir / (fname + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[ok] {arch} {shape_name} {mesh_kind}{' ' + tag if tag else ''}: "
          f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
          f"flops/dev {rec['flops_per_device']:.3g} "
          f"temp {mem.temp_size_in_bytes/2**30:.2f}GiB "
          f"args {mem.argument_size_in_bytes/2**30:.2f}GiB")
    return rec


def run_gw_cell(mesh_kind: str, out_dir: Path, s_r: int = 8192,
                s_c: int = 8192, outer: int = 10, inner: int = 30,
                tag: str = "", comm_dtype=None, submesh=None):
    """Dry-run the paper's own technique at pod scale: sharded Grid-SPAR-GW
    (s_r x s_c grid block over the full mesh; s = s_r*s_c samples — the
    n ≈ 4M-point regime at the paper's s = 16n).

    ``submesh=(d, m)`` runs the problem on a d×m submesh instead of the
    whole pod (production pattern: many independent GW problems, one per
    submesh — e.g. pairwise graph-distance workloads, paper §6.2 — rather
    than over-sharding a single small problem across 256 chips)."""
    import jax.numpy as jnp
    from repro.core.sharded_gw import make_sharded_grid_gw

    if submesh is not None:
        mesh = jax.make_mesh(submesh, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        if "pod" in mesh.axis_names:
            # fold the pod axis into data (pure row sharding)
            mesh = jax.make_mesh((32, 16), ("data", "model"))
    solver = make_sharded_grid_gw(mesh, s_r, s_c, "l2", 1e-2, outer, inner,
                                  comm_dtype=comm_dtype)
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((s_r, s_r), f32),
            jax.ShapeDtypeStruct((s_c, s_c), f32),
            jax.ShapeDtypeStruct((s_r,), f32),
            jax.ShapeDtypeStruct((s_c,), f32),
            jax.ShapeDtypeStruct((s_r, s_c), f32))
    t0 = time.time()
    with mesh:
        lowered = solver.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # shard_map body contains no scans over layers; fori over iters is
    # counted once -> multiply by outer*inner analytically for the sinkhorn
    # matvec part and outer for cost assembly: conservative (report both).
    rec = {"arch": "spargw-engine", "shape": f"grid{s_r}x{s_c}",
           "mesh": mesh_kind, "mesh_shape": dict(mesh.shape),
           "device_kind": mesh.devices.flat[0].device_kind,
           "kind": "gw", "tag": tag, "n_params": 0,
           "lower_s": 0.0, "compile_s": round(time.time() - t0, 2),
           "memory": {
               "argument_bytes": mem.argument_size_in_bytes,
               "output_bytes": mem.output_size_in_bytes,
               "temp_bytes": mem.temp_size_in_bytes,
               "alias_bytes": mem.alias_size_in_bytes,
               "code_bytes": mem.generated_code_size_in_bytes},
           "flops_raw": cost.get("flops", 0.0),
           "bytes_raw": cost.get("bytes accessed", 0.0),
           # loop bodies counted once: one outer iter contains the cost
           # assembly + `inner`-counted-once sinkhorn. Scale by outer; add
           # (inner-1) matvec pairs analytically: 2*2*s_r*s_c flops each.
           "flops_per_device": (cost.get("flops", 0.0)
                                + (inner - 1) * 4.0 * s_r * s_c
                                / (mesh.shape["data"] * mesh.shape["model"])
                                ) * outer,
           "bytes_per_device": cost.get("bytes accessed", 0.0) * outer,
           "collectives": parse_collectives(hlo),
           "hlo_lines": hlo.count("\n")}
    # wire bytes also scale with the outer loop (counted once in HLO)
    for v in rec["collectives"].values():
        v["wire_bytes"] *= outer * (1 + inner / 4)   # sinkhorn psum pairs
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"spargw-engine__grid{s_r}x{s_c}__{mesh_kind}"
    if tag:
        name += f"__{tag}"
    with open(out_dir / (name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[ok] spargw-engine grid{s_r}x{s_c} {mesh_kind}: compile "
          f"{rec['compile_s']}s flops/dev {rec['flops_per_device']:.3g} "
          f"temp {mem.temp_size_in_bytes/2**30:.2f}GiB")
    return rec


def recost_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
                use_flash: bool = True, rules=None):
    """Recompute the scan-aware flop/byte extrapolation for an existing
    cell JSON (production compile results are reused untouched)."""
    fname = out_dir / f"{arch}__{shape_name}__{mesh_kind}.json"
    with open(fname) as f:
        rec = json.load(f)
    cfg = cb.get_arch(arch)
    shape = cb.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    dp = shd.data_axes(mesh)
    set_activation_sharding(
        True, dp=dp, dp_size=int(np.prod([mesh.shape[a] for a in dp])),
        model_size=mesh.shape["model"])
    t0 = time.time()
    _add_cost_fields(rec, cfg, shape, mesh, use_flash, rules)
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[recost] {arch} {shape_name} {mesh_kind}: "
          f"flops/dev {rec['flops_per_device']:.3g} "
          f"bytes/dev {rec['bytes_per_device']:.3g} "
          f"(unblocked {rec['bytes_unblocked_per_device']:.3g}) "
          f"({time.time()-t0:.0f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="single",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--cost-only", action="store_true")
    ap.add_argument("--gw", action="store_true",
                    help="dry-run the sharded GW engine instead of LM cells")
    ap.add_argument("--out", type=str, default=str(ART))
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.gw:
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        for mk in meshes:
            run_gw_cell(mk, out_dir)
        return

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        archs = [a for a in cb.CLI_ALIASES]
    else:
        archs = [args.arch]

    failures = []
    for arch in archs:
        cfg = cb.get_arch(arch)
        shapes = [s.name for s in cb.shapes_for(cfg)] \
            if args.shape is None else [args.shape]
        for shape_name in shapes:
            for mesh_kind in meshes:
                fname = out_dir / (f"{arch}__{shape_name}__{mesh_kind}.json")
                if args.cost_only:
                    try:
                        recost_cell(arch, shape_name, mesh_kind, out_dir)
                    except Exception as e:  # noqa: BLE001
                        traceback.print_exc()
                        failures.append((arch, shape_name, mesh_kind,
                                         str(e)[:200]))
                    continue
                if args.skip_existing and fname.exists():
                    print(f"[skip] {arch} {shape_name} {mesh_kind}")
                    continue
                try:
                    run_cell(arch, shape_name, mesh_kind, out_dir)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_kind, str(e)[:200]))
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("all cells passed")


if __name__ == "__main__":
    main()
