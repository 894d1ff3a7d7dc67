"""Plain float32 reference of the dense decoder-only LM (GQA, RoPE,
RMSNorm, SwiGLU), written from the model's description and nothing of
the program under test.

Weights are made again from the seed by the documented init scheme
(normal draws scaled by fan-in, stored in the configuration's dtype), one
layer at a time, so a full-width model fits beside the activations of the
sampled requests. Every matrix product runs at ``Precision.HIGHEST``.

``precision="fp8"`` is the control: every matrix product takes its
operands rounded to float8 e4m3 with a per-row (activations) and
per-column (weights) absmax scale, the step below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``; the
    gradient passes straight through the rounding."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def matmul(x, w, precision: str):
    """x (..., k) @ w (k, n) in float32, or through fp8 for the control."""
    if precision == "fp8":
        x = _q8(x, -1)
        w = _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _einsum(spec, a, b, precision, a_axis, b_axis):
    if precision == "fp8":
        a = _q8(a, a_axis)
        b = _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _normal(key, shape, std, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * std
    return w.astype(dtype).astype(jnp.float32)


def model_keys(seed: int, n_layers: int):
    """Keys of the init scheme: embed, unembed, then one per layer."""
    root = jax.random.PRNGKey(seed)
    k_embed, k_unembed, k_layers, _shared, _final = jax.random.split(root, 5)
    return k_embed, k_unembed, jax.random.split(k_layers, n_layers)


def layer_weights(key, c: Dict) -> Dict[str, jnp.ndarray]:
    d, f, L = c["d_model"], c["d_ff"], c["n_layers"]
    qd = c["n_heads"] * c["head_dim"]
    kvd = c["n_kv_heads"] * c["head_dim"]
    dt = DTYPES[c["dtype"]]
    ka, km = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    kg, ku, kd = jax.random.split(km, 3)
    return {
        "wq": _normal(kq, (d, qd), d ** -0.5, dt),
        "wk": _normal(kk, (d, kvd), d ** -0.5, dt),
        "wv": _normal(kv, (d, kvd), d ** -0.5, dt),
        "wo": _normal(ko, (qd, d), qd ** -0.5 / math.sqrt(2 * L), dt),
        "wg": _normal(kg, (d, f), d ** -0.5, dt),
        "wu": _normal(ku, (d, f), d ** -0.5, dt),
        "wd": _normal(kd, (f, d), f ** -0.5 / math.sqrt(2 * L), dt),
    }


def rmsnorm(x, eps):
    # the norm gains are initialised to one
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, theta):
    """x (N, H, S, hd), rotate-half layout, positions 0..S-1."""
    S, hd = x.shape[2], x.shape[3]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    ang = jnp.concatenate([ang, ang], -1)
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v, precision):
    """One sequence: q (H, S, hd), k/v (KH, S, hd); causal, GQA."""
    H, S, hd = q.shape
    rep = H // k.shape[0]
    k = jnp.repeat(k, rep, axis=0)
    v = jnp.repeat(v, rep, axis=0)
    s = _einsum("hqd,hkd->hqk", q, k, precision, -1, -1) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return _einsum("hqk,hkd->hqd", p, v, precision, -1, 1)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _layer(key, h, c, precision):
    cd = dict(c)
    w = layer_weights(key, cd)
    N, S, d = h.shape
    H, KH, hd = cd["n_heads"], cd["n_kv_heads"], cd["head_dim"]
    x = rmsnorm(h, cd["norm_eps"])
    q = matmul(x, w["wq"], precision).reshape(N, S, H, hd).transpose(0, 2, 1, 3)
    k = matmul(x, w["wk"], precision).reshape(N, S, KH, hd).transpose(0, 2, 1, 3)
    v = matmul(x, w["wv"], precision).reshape(N, S, KH, hd).transpose(0, 2, 1, 3)
    q = rope(q, cd["rope_theta"])
    k = rope(k, cd["rope_theta"])
    # one sequence at a time keeps the (H, S, S) scores of one in memory
    o = lax.map(lambda a: _attention(*a, precision), (q, k, v))
    o = o.transpose(0, 2, 1, 3).reshape(N, S, H * hd)
    h = h + matmul(o, w["wo"], precision)
    x = rmsnorm(h, cd["norm_eps"])
    a = matmul(x, w["wg"], precision)
    mlp = (a * jax.nn.sigmoid(a)) * matmul(x, w["wu"], precision)
    return h + matmul(mlp, w["wd"], precision)


@functools.partial(jax.jit, static_argnames=("c",))
def _embed(key, tokens, c):
    cd = dict(c)
    table = _normal(key, (cd["vocab"], cd["d_model"]), 0.02,
                    DTYPES[cd["dtype"]])
    return table[tokens]


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _logits(key, h, c, precision):
    cd = dict(c)
    w = _normal(key, (cd["d_model"], cd["vocab"]), cd["d_model"] ** -0.5,
                DTYPES[cd["dtype"]])
    return matmul(rmsnorm(h, cd["norm_eps"]), w, precision)


MODEL_KEYS = ("d_model", "d_ff", "n_layers", "n_heads", "n_kv_heads",
              "head_dim", "vocab", "dtype", "norm_eps", "rope_theta")


def frozen(cfg: Dict) -> Tuple:
    return tuple(sorted((k, cfg[k]) for k in MODEL_KEYS))


def logits_at(seed: int, cfg: Dict, tokens: np.ndarray, start: int,
              precision: str = "f32") -> np.ndarray:
    """Logits (N, S - start, vocab) that positions ``start..S-1`` of
    ``tokens`` (N, S) predict, the model's weights made from ``seed``."""
    c = frozen(cfg)
    k_embed, k_unembed, layer_keys = model_keys(seed, cfg["n_layers"])
    h = _embed(k_embed, jnp.asarray(tokens, jnp.int32), c)
    for i in range(cfg["n_layers"]):
        h = _layer(layer_keys[i], h, c, precision)
    return np.asarray(_logits(k_unembed, h[:, start:], c, precision))


def served_gaps(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position: (N, T)."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, served[..., None], -1)[..., 0]
    return best - got
