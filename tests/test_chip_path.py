"""The chip path's CPU-checkable parts: the Pallas tile ops and flash
attention differentiate (custom VJP against the jnp oracles), scalars
reach a tile kernel traced inside ``jit``, devices are looked up by
``device_kind``, the compile cache lands where it should, and
``chip_smoke.py`` refuses to run without a TPU. Pallas runs in interpret
mode here."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hardware import TPU_V5E, chip_for_device_kind
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.tile_programs import get_tile_op

ROOT = Path(__file__).resolve().parents[1]

# name -> (input shapes, scalars); the last input shape's width is d
GRAD_CASES = {
    "rmsnorm": ([(2, 16, 256), (256,)], {"eps": 1e-6}),
    "layernorm": ([(2, 16, 256), (256,), (256,)], {"eps": 1e-5}),
    "swiglu": ([(2, 16, 256)] * 2, {}),
    "gelu": ([(4, 128)], {}),
}


@pytest.mark.parametrize("name", sorted(GRAD_CASES))
def test_tile_op_grad_matches_ref(name, rng):
    shapes, scalars = GRAD_CASES[name]
    args = [jnp.asarray(rng.normal(size=s), jnp.float32) for s in shapes]
    ref_fn = getattr(ref, f"{name}_ref")
    op = get_tile_op(name)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, **scalars) ** 2)

    argnums = tuple(range(len(args)))
    got = jax.jit(jax.grad(loss(op.apply), argnums))(*args)
    want = jax.grad(loss(ref_fn), argnums)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_adamw_takes_traced_scalars_inside_jit(rng):
    p, g, m, v = (jnp.asarray(rng.normal(size=(24, 128)), jnp.float32)
                  for _ in range(4))
    v = jnp.abs(v)
    fixed = dict(b1=0.9, b2=0.95, eps=1e-8, wd=0.1)
    op = get_tile_op("adamw")

    @jax.jit
    def step(lr, inv_bc1, inv_bc2):
        return op.apply(p, g, m, v, lr=lr, inv_bc1=inv_bc1, inv_bc2=inv_bc2,
                        **fixed)

    for lr, bc1, bc2 in ((3e-4, 10.0, 20.0), (1e-2, 1.5, 2.5)):
        got = step(jnp.float32(lr), jnp.float32(bc1), jnp.float32(bc2))
        want = ref.adamw_ref(p, g, m, v, lr=lr, inv_bc1=bc1, inv_bc2=bc2,
                             **fixed)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("H,KH,causal", [(4, 4, True), (4, 2, True),
                                         (2, 2, False)],
                         ids=("mha", "gqa", "noncausal"))
def test_flash_attention_grad_matches_ref(H, KH, causal, rng):
    B, S, D = 1, 128, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, q_block=64, kv_block=64)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_chip_for_device_kind():
    assert chip_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="unknown device_kind"):
        chip_for_device_kind("TPU v4")


def _run(args, env_extra, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


_SHOW_CACHE = ("import jax\n"
               "from repro.launch.compile_cache import enable_compile_cache\n"
               "print(enable_compile_cache())\n"
               "print(jax.config.jax_compilation_cache_dir)\n")


@pytest.mark.parametrize("env_dir", [False, True], ids=("default", "env"))
def test_compile_cache_dir(env_dir, tmp_path):
    want = str(tmp_path / "cc") if env_dir else str(ROOT / ".jax_cache")
    extra = {"JAX_COMPILATION_CACHE_DIR": want} if env_dir else {}
    out = _run(["-c", _SHOW_CACHE], extra)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]


def test_chip_smoke_refuses_without_tpu():
    out = _run([str(ROOT / "chip_smoke.py")], {})
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout
