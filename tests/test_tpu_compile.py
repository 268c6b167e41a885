"""Compile the main-path kernels for a described TPU v5e chip (no chip
needed): the TPU compiler runs here and raises what the chip's compiler
would raise — a Mosaic lowering error, a block that breaks the tiling
rule, a kernel over the VMEM limit. Nothing runs; each case checks that
the compiled text holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, so describing it while the
module is imported would break collection under several test workers.
"""
import importlib
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

V5E = "v5e:2x2"
V5E_HBM_BYTES = int(15.75 * 2**30)      # what the compiler lets a program use


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name=V5E)
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no {V5E} topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out of any cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_spar_matvec_compiles(one_chip):
    from repro.kernels.spar_cost.spar_cost import spar_matvec_pallas
    s_p, b = 16384, 256
    compiled = spar_matvec_pallas.lower(
        _spec(one_chip, (s_p, s_p)), _spec(one_chip, (s_p,)),
        _spec(one_chip, (s_p,)), bk=b, bl=b, interpret=False).compile()
    _assert_kernel(compiled)


# the server's shapes: bucket 1024 with s = 16n at n = 1000, and bucket
# 2048 with s = 16n at n = 2000; and bucket 4096 at n = 4000, the largest
# whose panels fit VMEM
@pytest.mark.parametrize("m,s", [(1024, 16000), (2048, 32000),
                                 (4096, 64000)])
def test_fused_spar_cost_compiles(one_chip, m, s):
    from repro.kernels.spar_cost.spar_cost import (
        K_BLOCK as bk, L_BLOCK, _vmem_limit, spar_cost_pallas)
    s_k, s_l = -(-s // bk) * bk, -(-s // L_BLOCK) * L_BLOCK
    panel = _spec(one_chip, (s_k // bk, m, 8, bk // 8))
    compiled = spar_cost_pallas.lower(
        panel, panel, _spec(one_chip, (s_l,), jnp.int32),
        _spec(one_chip, (s_l,), jnp.int32), _spec(one_chip, (s_l,)),
        _spec(one_chip, (s_k,)), loss="l2", interpret=False).compile()
    _assert_kernel(compiled)
    # resident panels, double-buffered: 2 · 2 · m · bk · 4 B, past Mosaic's
    # default scoped VMEM, so the kernel states its limit, within the
    # chip's 128 MiB of VMEM; the kernel adds no HBM temporaries of its own
    assert 2 * 2 * m * bk * 4 < _vmem_limit(m, m, bk) < 128 * 2**20
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _served_spar_batch(sharding, n, bucket, impl, lanes=2, lam=None):
    """GWServer's compiled executable for a spar_gw bucket of ``lanes``
    Gaussian point clouds of n points, s = 16n, on cost_impl ``impl``;
    unbalanced (Algorithm 3) where ``lam`` is given."""
    import repro
    from repro.serve import GWServer, ServeConfig
    from repro.serve.batching import pad_problem

    pts = np.random.default_rng(0).standard_normal((n, 2)).astype(np.float32)
    C = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    geom = repro.Geometry(jnp.asarray(C), jnp.full(n, 1.0 / n, jnp.float32))
    terms = {} if lam is None else {"lam": lam}
    problem = pad_problem(repro.QuadraticProblem(geom, geom, **terms),
                          bucket, bucket)
    solver = repro.SparGWSolver(s=16 * n, cost_impl=impl)
    item = (problem, solver, jax.random.PRNGKey(0))
    # the shapes ``stack_items`` gives, without stacking the lanes
    stacked = jax.tree.map(lambda x: _spec(sharding, (lanes,) + np.shape(x),
                                           jnp.asarray(x).dtype), item)
    server = GWServer(ServeConfig(flush_thread=False))
    return server._exec.lower(*stacked).compile()


def test_served_spar_gw_batch_compiles(one_chip, monkeypatch):
    """GWServer's vmapped executable for a 2-lane spar_gw bucket at
    n = 1024, with the cost assembly on the gather-fused kernel."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    _assert_kernel(_served_spar_batch(one_chip, 1000, 1024, "pallas"))


COST_KERNELS = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                           / "gwbench" / "cost_kernels.json").read_text())
SCOPES = ("gw.cost", "gw.sinkhorn", "gw.pga_step")
# the benchmark's spar cells: n = 1000 on the fused kernel, n = 500 on the
# materialized matvec (what "auto" picks on the chip at these sizes)
SERVED = {"spar_cost_pallas": (1000, 1024, "pallas"),
          "spar_matvec_pallas": (500, 512, "materialized")}


def test_served_kernels_cover_the_attributed_names():
    assert sorted(SERVED) == sorted(
        n for names in COST_KERNELS.values() for n in names)


@pytest.mark.parametrize("kernel", sorted(SERVED))
def test_served_spar_batch_keeps_kernel_names_and_scopes(one_chip, kernel,
                                                         monkeypatch):
    """The names the benchmark attributes device time by survive into
    the compiled executable of a served 2-lane spar_gw batch: an
    instruction named after the Pallas kernel, and the three named scopes
    in the HLO ``op_name`` metadata."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    _assert_kernel_and_scopes(
        _served_spar_batch(one_chip, *SERVED[kernel]).as_text(), kernel,
        SCOPES)


def _assert_kernel_and_scopes(text, kernel, scopes):
    """Every Pallas call in the compiled text is named after ``kernel``,
    and each scope is in some op's ``op_name`` metadata."""
    names = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, re.M)
    assert names and all(kernel in name for name in names), names
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in scopes:
        assert any(scope in o for o in op_names), scope


def _computations(text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _reach(comps, root):
    """Every computation ``root`` runs: loop bodies and conditions,
    fusions, calls, reductions' appliers and conditional branches."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"(?:condition|body|calls|to_apply)=%([\w.-]+)",
                               line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


@pytest.mark.parametrize("kernel", sorted(SERVED))
def test_served_spar_inner_sinkhorn_loop_has_no_scatter(one_chip, kernel,
                                                        monkeypatch):
    """In a served 2-lane spar_gw batch the inner Sinkhorn loop (the one
    nested in the outer PGA loop) runs on the dense cell grid: no scatter
    and no gather in its body, which keeps the ``gw.sinkhorn`` scope; the
    merge of the support into the grid sits outside it, in the outer
    step (a scatter onto the 2 · bucket² cells)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    n, bucket, impl = SERVED[kernel]
    _assert_inner_loop_has_no_scatter(
        _served_spar_batch(one_chip, n, bucket, impl).as_text(), bucket)


def _assert_inner_loop_has_no_scatter(text, bucket):
    comps = _computations(text)
    loops = {}      # while instruction -> (computation holding it, body)
    for name, lines in comps.items():
        for line in lines:
            w = re.search(r"%([\w.-]+) = .* while\(.*body=%([\w.-]+)", line)
            if w:
                loops[w.group(1)] = (name, w.group(2))
    reach = {w: _reach(comps, body) for w, (_, body) in loops.items()}
    nests = [(o, i) for o in loops for i in loops
             if i != o and loops[i][0] in reach[o]]
    assert len(nests) == 1, loops
    outer, inner = nests[0]

    def lines_of(loop):
        return [line for c in reach[loop] for line in comps[c]]

    assert not any(f" {op}(" in line for line in lines_of(inner)
                   for op in ("scatter", "gather"))
    assert any("gw.sinkhorn" in line for line in lines_of(inner))
    # the merge: a scatter onto both lanes' (bucket, bucket) grids
    grids = [re.search(r"= f32\[([\d,]+)\]\S* scatter\(", line)
             for line in lines_of(outer)]
    assert 2 * bucket * bucket in {
        int(np.prod([int(d) for d in g.group(1).split(",")]))
        for g in grids if g}


def test_served_ugw_batch_compiles_with_its_scopes(one_chip, monkeypatch):
    """GWServer's executable for a 2-lane unbalanced spar_gw bucket
    (Algorithm 3) at n = 1000, bucket 1024, as the ``moon_ugw_n1000``
    cell serves it: the gather-fused cost kernel and the ``gw.ugw_init``
    (start, eq.-9 law, 2-D draw, weights), ``gw.cost`` and
    ``gw.sinkhorn`` scopes survive into the compiled text; the inner
    Sinkhorn loop runs on the dense grid as in the balanced case; the
    batch fits the chip."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    compiled = _served_spar_batch(one_chip, 1000, 1024, "pallas", lam=1.0)
    text = compiled.as_text()
    _assert_kernel_and_scopes(text, "spar_cost_pallas",
                              ("gw.ugw_init", "gw.cost", "gw.sinkhorn"))
    _assert_inner_loop_has_no_scatter(text, 1024)
    assert _peak_bytes(compiled) < V5E_HBM_BYTES


def _peak_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_served_spar_dense_grid_is_free_at_its_bound(one_chip, monkeypatch):
    """At the largest square bucket that keeps the inner Sinkhorn's dense
    cell grid (4096², 2**24 cells a lane) and GWServer's default 8 lanes,
    the grid adds nothing to the compiled peak of a served spar_gw batch:
    it lives in what the cost step frees, and the batch fits the chip.
    (Cost on the jnp path: the gather-fused kernel's (m, s) panels alone
    want 24 GiB at 8 lanes, on either layout.)"""
    from repro.obs import registry
    sk = importlib.import_module("repro.core.sinkhorn")
    peak = {}
    for layout, cells_max in (("coo", 0), ("dense", sk._DENSE_CELLS_MAX)):
        monkeypatch.setattr(sk, "_DENSE_CELLS_MAX", cells_max)
        jax.clear_caches()      # the layout is fixed when a shape traces
        traces = registry().counter("repro_sinkhorn_layout_total",
                                    layout=layout)
        before = traces.value
        peak[layout] = _peak_bytes(_served_spar_batch(
            one_chip, 4096, 4096, "jnp", lanes=8))
        assert traces.value > before, layout
    assert peak["dense"] <= 1.01 * peak["coo"], peak
    assert peak["dense"] < V5E_HBM_BYTES, peak
