"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e (no chip attached): the TPU compiler refuses here what interpret
mode accepts — a bf16 ref given f32 values, a block past the scoped VMEM
limit, a scalar closed over by the kernel. Each case compiles at a real
model width and must contain the Pallas kernel (``tpu_custom_call``).

Widths: minitron-4b's serve path (d_model 3072, d_ff 9216, head_dim
128) and whisper-small's train path (d_model 768, d_ff 3072, 12 heads
of 64). The topology is described in a fixture, never at import, so
every test worker collects the same tests."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.tile_programs import get_tile_op


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _tile(name, **scalars):
    def apply(*arrays):
        return get_tile_op(name).apply(*arrays, interpret=False, **scalars)
    return apply


def _adamw(p, g, m, v, lr, inv_bc1, inv_bc2):
    return get_tile_op("adamw").apply(
        p, g, m, v, interpret=False, lr=lr, b1=0.9, b2=0.95, eps=1e-8,
        wd=0.1, inv_bc1=inv_bc1, inv_bc2=inv_bc2)


def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _flash_grad(q, k, v):
    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _layernorm_grad(x, g, b):
    def loss(x, g, b):
        return jnp.sum(_tile("layernorm", eps=1e-5)(x, g, b) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))(x, g, b)


f32, bf16 = jnp.float32, jnp.bfloat16
# name -> (function, [(shape, dtype), ...])
CASES = {
    "rmsnorm_f32_d3072": (_tile("rmsnorm", eps=1e-6),
                          [((4, 512, 3072), f32), ((3072,), f32)]),
    "swiglu_bf16_dff9216": (_tile("swiglu"),
                            [((4, 512, 9216), bf16)] * 2),
    "rotary_bf16_hd128": (_tile("rotary"),
                          [((4, 24, 512, 128), bf16),
                           ((4, 24, 512, 128), f32),
                           ((4, 24, 512, 128), f32)]),
    "layernorm_f32_d768": (_tile("layernorm", eps=1e-5),
                           [((8, 256, 768), f32), ((768,), f32),
                            ((768,), f32)]),
    "gelu_bf16_dff3072": (_tile("gelu"), [((8, 256, 3072), bf16)]),
    "adamw_traced_scalars": (_adamw, [((768, 3072), f32)] * 4
                             + [((), f32)] * 3),
    "flash_fwd_24q_8kv": (_flash, [((1, 24, 512, 128), bf16),
                                   ((1, 8, 512, 128), bf16),
                                   ((1, 8, 512, 128), bf16)]),
    "flash_grad_12h_hd64": (_flash_grad, [((8, 12, 256, 64), bf16)] * 3),
    "layernorm_grad_d768": (_layernorm_grad,
                            [((8, 256, 768), f32), ((768,), f32),
                             ((768,), f32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip):
    fn, specs = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
