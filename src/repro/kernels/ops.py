"""Public kernel API — jitted wrappers dispatching per backend.

Every op has three implementations:
  * ``pallas``  — the Pallas TPU kernel (interpret-mode on CPU): saturated
                  tile programs via :mod:`repro.core.pallasgen`, plus the
                  handwritten flash-attention / SSD kernels;
  * ``jnp``     — the *saturated generated JAX code* (the paper's optimized
                  output, CPU-fast, used inside jitted model steps);
  * ``ref``     — the independent oracle in :mod:`repro.kernels.ref`.

Default: pallas on TPU, jnp elsewhere. ``set_impl(...)`` overrides
globally (tests sweep all three).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.telemetry import telemetry
from repro.parallel import ctx
from repro.runtime.guard import breaker_for

from . import ref as _ref
from .flash_attention import decode_attention, flash_attention
from .ssd_scan import ssd_decode_step, ssd_scan, ssd_scan_jnp
from .tile_programs import get_tile_op

_IMPL: Optional[str] = None  # None = auto
_SAT_CACHE: Optional[str] = None  # persistent saturation cache directory
_SAT_VERIFY: Optional[str] = None  # static-verification level for builds

# runtime degradation floor (PR 10): the named jnp oracle each tile op
# falls back to when building or applying the optimized op fails — the
# serve/train hot paths must never see a saturator exception. jnp
# oracles are jit-traceable, so the fallback also works mid-trace
# (where the pipeline's numpy reference interpreter cannot run).
_REF_FNS: dict = {
    "rmsnorm": _ref.rmsnorm_ref, "rmsnorm_gated": _ref.rmsnorm_gated_ref,
    "layernorm": _ref.layernorm_ref, "swiglu": _ref.swiglu_ref,
    "gelu": _ref.gelu_ref, "rotary": _ref.rotary_ref,
    "residual_scale": _ref.residual_scale_ref,
    "softmax": _ref.softmax_ref, "moe_router": _ref.softmax_ref,
    "adamw": _ref.adamw_ref, "ssd_gate": _ref.ssd_gate_ref,
}


def set_impl(impl: Optional[str]):
    """impl in {None(auto), 'pallas', 'jnp', 'ref'}."""
    global _IMPL
    assert impl in (None, "auto", "pallas", "jnp", "ref")
    _IMPL = None if impl == "auto" else impl


def set_saturation_cache(path: Optional[str]):
    """Point every tile op built after this call at a persistent
    saturation cache directory (repro.cache): saturation/beam results
    are replayed from disk instead of re-searched per process. None
    disables (the default; the REPRO_SAT_CACHE env var still applies
    at the pipeline level). The launch drivers call this at startup so
    the serve/train hot paths are warm across boots."""
    global _SAT_CACHE
    _SAT_CACHE = str(path) if path is not None else None


def current_saturation_cache() -> Optional[str]:
    return _SAT_CACHE


def set_saturation_verify(level: Optional[str]):
    """Static-verification level ("off" | "cheap" | "full", see
    repro.verify) applied to every tile op built after this call. The
    launch drivers resolve --verify / REPRO_VERIFY through
    SaturatorConfig.from_env and thread the result here; None/"off"
    adds zero overhead (the default)."""
    global _SAT_VERIFY
    _SAT_VERIFY = None if level in (None, "off") else str(level)


def current_saturation_verify() -> Optional[str]:
    return _SAT_VERIFY


def _op(name: str):
    return get_tile_op(name, cache_dir=_SAT_CACHE, verify=_SAT_VERIFY)


def current_impl() -> str:
    if _IMPL is not None:
        return _IMPL
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _guarded(name: str, optimized: Callable, reference: Callable):
    """Run the optimized path under the runtime floor: any failure
    (building the tile op, tracing, or applying it) falls back to the
    named jnp oracle instead of raising to the caller. A per-kernel
    circuit breaker skips the optimized attempt entirely after repeated
    failures, so a pathological kernel doesn't pay the failure cost on
    every request."""
    br = breaker_for(("apply", name))
    if br.admit() is not None:
        telemetry().record_runtime_fallback(name, "breaker_open")
        return reference()
    try:
        out = optimized()
    except Exception as e:  # ladder floor: degrade, never raise
        br.record_failure(fallback_level="ref")
        telemetry().record_runtime_fallback(name, type(e).__name__)
        return reference()
    br.record_success()
    return out


def _local(fn: Callable, arrays, specs, out_spec):
    """Run a Pallas kernel on each device's shard under the active mesh.

    The partitioner cannot split a Pallas kernel, so under a mesh the
    call goes through ``jax.shard_map`` with explicit specs; without one
    it runs as is."""
    mesh = ctx.active_mesh()
    if mesh is None:
        return fn(*arrays)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=out_spec, check_vma=False)(*arrays)


def _pallas_tile(name: str, arrays, scalars):
    """The Pallas tile op; under a mesh every device runs it over
    replicated operands, gathered where they came sharded: those
    gathers carry the ``tile_gather`` scope in their ``op_name``.
    Scalars travel as arguments so traced values (the learning rate)
    cross into ``shard_map``."""
    names = sorted(scalars)
    n = len(arrays)

    def run(*args):
        return _op(name).apply(*args[:n], **dict(zip(names, args[n:])))

    args = list(arrays) + [jnp.asarray(scalars[k], jnp.float32)
                           for k in names]
    if ctx.active_mesh() is None:
        return run(*args)
    with jax.named_scope("tile_gather"):
        # the partitioner names a gather after the op whose value it
        # gathers: a barrier here puts the operands' gathers in the scope
        args = jax.lax.optimization_barrier(args)
        return _local(run, args, (P(),) * len(args), P())


def _tile(name: str, *arrays, **scalars):
    impl = current_impl()
    ref_fn = _REF_FNS[name]
    if impl == "ref":
        return ref_fn(*arrays, **scalars)
    if impl == "pallas":
        return _guarded(name, lambda: _pallas_tile(name, arrays, scalars),
                        lambda: ref_fn(*arrays, **scalars))
    return _guarded(name, lambda: _op(name).jax_ref(*arrays, **scalars),
                    lambda: ref_fn(*arrays, **scalars))


# -- saturated tile ops ---------------------------------------------------------
def rmsnorm(x, g, eps=1e-6):
    return _tile("rmsnorm", x, g, eps=eps)


def rmsnorm_gated(x, z, g, eps=1e-6):
    return _tile("rmsnorm_gated", x, z, g, eps=eps)


def layernorm(x, g, b, eps=1e-6):
    return _tile("layernorm", x, g, b, eps=eps)


def swiglu(a, b):
    return _tile("swiglu", a, b)


def gelu(a):
    return _tile("gelu", a)


def rotary(q, cos, sin):
    """q:(..., d); cos/sin broadcastable to q. Tile rows = flattened lead."""
    impl = current_impl()
    if impl == "ref":
        return _ref.rotary_ref(q, cos, sin)

    def _opt():
        cosb = jnp.broadcast_to(cos, q.shape)
        sinb = jnp.broadcast_to(sin, q.shape)
        if impl == "pallas":
            return _pallas_tile("rotary", (q, cosb, sinb), {})
        return _op("rotary").jax_ref(q, cosb, sinb)

    return _guarded("rotary", _opt, lambda: _ref.rotary_ref(q, cos, sin))


def residual_scale(x, y, alpha=1.0):
    return _tile("residual_scale", x, y, alpha=alpha)


def softmax(x):
    return _tile("softmax", x)


def moe_router_probs(logits):
    return _tile("moe_router", logits)


def adamw_update(param, grad, m, v, *, lr, b1, b2, eps, wd,
                 inv_bc1, inv_bc2):
    """Returns (m_new, v_new, param_new) — saturated fused update."""
    return _tile("adamw", param, grad, m, v, lr=lr, b1=b1, b2=b2,
                 eps=eps, wd=wd, inv_bc1=inv_bc1, inv_bc2=inv_bc2)


def ssd_gate(dt_raw, a_log, bias=0.0):
    """Returns (dt, decay) with shared softplus. a_log broadcast to dt_raw."""
    impl = current_impl()
    if impl == "ref":
        return _ref.ssd_gate_ref(dt_raw, a_log, bias=bias)

    def _opt():
        a_b = jnp.broadcast_to(a_log, dt_raw.shape)
        if impl == "pallas":
            return _pallas_tile("ssd_gate", (dt_raw, a_b), {"bias": bias})
        return _op("ssd_gate").jax_ref(dt_raw, a_b, bias=bias)

    return _guarded("ssd_gate", _opt,
                    lambda: _ref.ssd_gate_ref(dt_raw, a_log, bias=bias))


# -- structured kernels -----------------------------------------------------------
def attention(q, k, v, *, causal=True, scale=None):
    """Full-sequence attention. Under ``pallas`` it is
    :func:`flash_attention` at the kernel's own tiles,
    ``attention_tiles(S)``: the largest of 512, 256 and 128 that divides
    S, or S when S <= 128. The backward follows the forward's tiles."""
    impl = current_impl()
    if impl != "pallas":
        return _ref.attention_ref(q, k, v, causal=causal, scale=scale)
    spec = P()
    mesh = ctx.active_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        # heads are independent: each device attends over its own heads
        tp = mesh.shape["model"]
        if q.shape[1] == k.shape[1] and q.shape[1] % tp == 0:
            spec = P(None, "model", None, None)
    return _local(functools.partial(flash_attention, causal=causal,
                                    scale=scale),
                  (q, k, v), (spec,) * 3, spec)


def attention_decode(q, k_cache, v_cache, *, scale=None):
    return decode_attention(q, k_cache, v_cache, scale=scale)


def ssd(x, dt, a_log, b_mat, c_mat, d_skip, *, chunk=128):
    impl = current_impl()
    if impl == "pallas":
        return ssd_scan(x, dt, a_log, b_mat, c_mat, d_skip, chunk=chunk)
    if impl == "ref":
        return _ref.ssd_ref(x, dt, a_log, b_mat, c_mat, d_skip)
    return ssd_scan_jnp(x, dt, a_log, b_mat, c_mat, d_skip, chunk=chunk)


def ssd_decode(h, x_t, dt_t, a_log, b_t, c_t, d_skip):
    return ssd_decode_step(h, x_t, dt_t, a_log, b_t, c_t, d_skip)
