"""Multi-device distribution tests (subprocess with forced host devices):
sharded GW vs reference, pipeline parallelism, gradient compression, and a
sharded train step."""
import numpy as np
import pytest

from repro.distrib.compression import dequantize_int8, quantize_int8


def test_int8_quantization_error_bound():
    import jax, jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s, x.shape)
    err = np.max(np.abs(np.array(back) - np.array(x)))
    # block max / 127 bound
    bound = float(jnp.max(jnp.abs(x))) / 127.0 * 1.01
    assert err <= bound


def test_sharded_gw_matches_reference(multi_device_runner):
    multi_device_runner("""
import jax, jax.numpy as jnp, numpy as np
from repro.core.sharded_gw import make_sharded_grid_gw
from repro.core.grid_gw import grid_cost
from repro.core.sinkhorn import sinkhorn_log
mesh = jax.make_mesh((2,2), ("data","model"))
s_r = s_c = 16
key = jax.random.PRNGKey(0)
CxR = jax.random.uniform(key,(s_r,s_r)); CxR=(CxR+CxR.T)/2
CyC = jax.random.uniform(jax.random.PRNGKey(1),(s_c,s_c)); CyC=(CyC+CyC.T)/2
aR = jnp.ones(s_r)/s_r; bC = jnp.ones(s_c)/s_c; w = jnp.ones((s_r,s_c))
solver = make_sharded_grid_gw(mesh, s_r, s_c, "l2", 0.05, 4, 15)
with mesh:
    val, T = solver(CxR, CyC, aR, bC, w)
Tr = aR[:,None]*bC[None,:]
for _ in range(4):
    C = grid_cost(CxR, CyC, Tr, "l2")
    Tr = sinkhorn_log(aR, bC, -C/0.05 + jnp.log(w) + jnp.log(jnp.maximum(Tr,1e-38)), 15)
ref = float(jnp.sum(Tr*grid_cost(CxR,CyC,Tr,"l2")))
assert abs(float(val)-ref) < 1e-4, (float(val), ref)
print("ok")
""")


def test_compressed_psum_under_shard_map(multi_device_runner):
    multi_device_runner("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.distrib.compression import dp_allreduce_grads
mesh = jax.make_mesh((4,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
def f(x_local):
    g = {"w": x_local[0]}
    out = dp_allreduce_grads(g, "data", compress=True)
    return out["w"]
y = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)(x)
ref = np.mean(np.array(x), axis=0)
err = np.max(np.abs(np.array(y) - ref))
bound = np.abs(np.array(x)).max()/127.0*1.5 + 1e-6
assert err < bound, (err, bound)
print("ok")
""")


def test_pipeline_parallel_matches_sequential(multi_device_runner):
    multi_device_runner("""
import jax, jax.numpy as jnp, numpy as np
from repro.distrib.pipeline import pipeline_forward
mesh = jax.make_mesh((4,), ("pipe",))
n_stages, n_micro, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
Ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
def stage_fn(W, x):
    return jnp.tanh(x @ W)
x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
piped = pipeline_forward(mesh, stage_fn, n_stages, n_micro)
with mesh:
    y = piped(Ws, x)
# sequential reference
ref = x
for i in range(n_stages):
    ref = jnp.tanh(ref @ Ws[i])
np.testing.assert_allclose(np.array(y), np.array(ref), atol=1e-5)
print("ok")
""")


def test_sharded_train_step_runs(multi_device_runner):
    multi_device_runner("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import base as cb
from repro.launch.steps import make_train_step
from repro.models.model_zoo import Model, set_activation_sharding
from repro.distrib import sharding as shd
from repro.optim import adamw
mesh = jax.make_mesh((2,2), ("data","model"))
set_activation_sharding(True, dp=("data",), dp_size=2, model_size=2)
cfg = cb.get_reduced("llama3_8b")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
abstract = model.abstract_params()
axes = model.param_axes()
param_sh = shd.param_shardings(axes, abstract, mesh)
params = jax.device_put(params, param_sh)
opt = adamw.init(params)
step = make_train_step(model, act_dtype=jnp.float32, remat=False, total_steps=5)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size)
batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
with mesh:
    fn = jax.jit(step, in_shardings=(param_sh, adamw.AdamWState(shd.replicated(mesh), param_sh, param_sh), None))
    p2, o2, m = fn(params, opt, batch)
assert np.isfinite(float(m["loss"]))
# gradient math must match single-device exactly
set_activation_sharding(False)
p_ref, _, m_ref = jax.jit(step)(jax.device_get(params), adamw.init(jax.device_get(params)), batch)
assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-4, (float(m["loss"]), float(m_ref["loss"]))
print("ok")
""")
