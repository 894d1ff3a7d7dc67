"""jaxpr bridge tests + multi-device subprocess tests (sharding rules and
pipeline parallelism run under XLA_FLAGS host-device counts in a child
process so the main test session keeps a single device)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import BridgeUnsupported, maybe_saturate, saturate_jax_fn


def test_bridge_elementwise(rng):
    def f(x, y):
        t = x * y + x * y
        return t * jax.lax.logistic(t) + x * y

    x = jnp.ones((4, 64), jnp.float32)
    bk = saturate_jax_fn(f, (x, x))
    xa = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    ya = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    np.testing.assert_allclose(np.asarray(bk(xa, ya)),
                               np.asarray(f(xa, ya)), rtol=2e-5, atol=2e-5)
    # CSE found the shared x*y
    assert bk.sk.kernel.stats.n_ops < bk.n_eqns


def test_bridge_scalar_args(rng):
    def f(x, alpha):
        return x * alpha + x

    x = jnp.ones((8, 16), jnp.float32)
    bk = saturate_jax_fn(f, (x, jnp.float32(0.5)))
    xa = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    np.testing.assert_allclose(np.asarray(bk(xa, jnp.float32(2.0))),
                               np.asarray(f(xa, jnp.float32(2.0))),
                               rtol=1e-6)


def test_bridge_rejects_unsupported():
    def f(x):
        return jnp.sort(x)

    x = jnp.ones((8,), jnp.float32)
    with pytest.raises(BridgeUnsupported):
        saturate_jax_fn(f, (x,))
    fn, info = maybe_saturate(f, (x,))
    assert info is None and fn is f


_SUBPROC_SHARDING = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.models import get_model
from repro.parallel import batch_specs, ctx, param_specs, to_named
from repro.launch import steps as S

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     devices=jax.devices()[:8],
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke_config("minitron_4b")
model = get_model(cfg)
with ctx.activate(mesh):
    params = model.init(jax.random.PRNGKey(0))
    pspecs = param_specs(cfg, params, mesh, fsdp=True)
    psh = to_named(pspecs, mesh)
    params = jax.device_put(params, psh)
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
             "labels": jnp.zeros((4, 32), jnp.int32)}
    bsh = to_named(batch_specs(cfg, batch, mesh), mesh)
    batch = jax.device_put(batch, bsh)
    loss = jax.jit(model.loss, in_shardings=(psh, bsh))(params, batch)
    assert np.isfinite(float(loss)), loss
    # unsharded single-device loss must match the sharded one
    params_local = jax.device_get(params)
    loss_ref = model.loss(jax.tree.map(jnp.asarray, params_local),
                          jax.tree.map(jnp.asarray, jax.device_get(batch)))
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=2e-2)
print("SHARDED_OK", float(loss))
"""

_SUBPROC_PP = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline_pp import (make_stage_fn, pipeline_apply,
                                        split_layers_to_stages)

mesh = jax.make_mesh((4,), ("stage",), devices=jax.devices()[:4],
                     axis_types=(jax.sharding.AxisType.Auto,))
L, D, M, mb = 8, 16, 6, 4
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, D, D)) * 0.3

def layer_fn(w, x):
    return jnp.tanh(x @ w)

stage_params = split_layers_to_stages(ws, 4)
stage_fn = make_stage_fn(layer_fn)
x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))
out = pipeline_apply(mesh, stage_fn, 4, M, x, stage_params)
# reference: plain sequential stack
ref = x
for l in range(L):
    ref = jnp.tanh(ref @ ws[l])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-4, atol=2e-4)
print("PP_OK")
"""


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_loss_matches_single_device():
    assert "SHARDED_OK" in _run_sub(_SUBPROC_SHARDING)


@pytest.mark.slow
def test_pipeline_parallel_equivalence():
    assert "PP_OK" in _run_sub(_SUBPROC_PP)
