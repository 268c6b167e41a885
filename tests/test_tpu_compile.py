"""Compile the main-path kernels for a described TPU v5e chip (no chip
needed): the TPU compiler runs here and raises what the chip's compiler
would raise — a Mosaic lowering error, a block that breaks the tiling
rule, a kernel over the VMEM limit. Nothing runs; each case checks that
the compiled text holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, so describing it while the
module is imported would break collection under several test workers.
"""
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


# the server's shapes: bucket 1024 with s = 16n at n = 1000 (padded to a
# multiple of the 256 block), and bucket 2048 with s = 16 · 2048
@pytest.mark.parametrize("m,s_p", [(1024, 16128), (2048, 32768)])
def test_fused_spar_cost_compiles(one_chip, m, s_p):
    from repro.kernels.spar_cost.spar_cost import spar_cost_pallas
    compiled = spar_cost_pallas.lower(
        _spec(one_chip, (m, s_p)), _spec(one_chip, (m, s_p)),
        _spec(one_chip, (s_p,), jnp.int32), _spec(one_chip, (s_p,), jnp.int32),
        _spec(one_chip, (s_p,)), _spec(one_chip, (s_p,)),
        loss="l2", bk=256, bl=256, interpret=False).compile()
    _assert_kernel(compiled)
    # resident panels, double-buffered: 2 · 2 · m · 256 · 4 B, within the
    # default scoped VMEM; the kernel adds no HBM temporaries of its own
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_served_spar_gw_batch_compiles(one_chip, monkeypatch):
    """GWServer's vmapped executable for a 2-lane spar_gw bucket at
    n = 1024, with the cost assembly on the gather-fused kernel."""
    import repro
    from repro.serve import GWServer, ServeConfig
    from repro.serve.batching import pad_problem, stack_items

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    n = 1000
    pts = np.random.default_rng(0).standard_normal((n, 2)).astype(np.float32)
    C = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    geom = repro.Geometry(jnp.asarray(C), jnp.full(n, 1.0 / n, jnp.float32))
    problem = pad_problem(repro.QuadraticProblem(geom, geom), 1024, 1024)
    solver = repro.SparGWSolver(s=16 * n, cost_impl="pallas")
    item = (problem, solver, jax.random.PRNGKey(0))
    stacked = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                           stack_items([item, item]))
    server = GWServer(ServeConfig(flush_thread=False))
    compiled = server._exec.lower(*stacked).compile()
    _assert_kernel(compiled)


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
    import repro
    from repro.serve import GWServer, ServeConfig
    from repro.serve.batching import pad_problem, stack_items

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    n, bucket, impl = SERVED[kernel]
    pts = np.random.default_rng(0).standard_normal((n, 2)).astype(np.float32)
    C = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    geom = repro.Geometry(jnp.asarray(C), jnp.full(n, 1.0 / n, jnp.float32))
    problem = pad_problem(repro.QuadraticProblem(geom, geom), bucket, bucket)
    solver = repro.SparGWSolver(s=16 * n, cost_impl=impl)
    item = (problem, solver, jax.random.PRNGKey(0))
    stacked = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                           stack_items([item, item]))
    server = GWServer(ServeConfig(flush_thread=False))
    text = server._exec.lower(*stacked).compile().as_text()
    names = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, re.M)
    assert names and all(kernel in name for name in names), names
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(scope in o for o in op_names), scope
