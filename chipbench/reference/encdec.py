"""Plain float32 reference of the Whisper-style encoder-decoder and its
AdamW training step, written from the model's description and nothing
of the program under test.

The encoder takes frame embeddings (the convolutional front end is not
part of the configuration) plus sinusoidal positions; both stacks use
pre-LayerNorm blocks (eps 1e-6), multi-head attention without rotary
embeddings and a tanh-GELU MLP; the decoder adds learned positions,
causal self-attention and cross-attention over the encoder's output, and
ties its output projection to the token embedding. The loss is the mean
token cross-entropy.

Weights are made from the seed by the documented init scheme and kept in
the configuration's dtype (bf16) between steps, as the configuration
states; gradients, moments and the update are float32 at
``Precision.HIGHEST``. Each layer is rematerialised in the backward pass
so the full-width model trains in a chip's memory. ``precision="fp8"`` is
the control: every matrix product takes float8 e4m3 operands.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_lm import DTYPES, _einsum, _normal, matmul


def init_params(key, c: Dict):
    """The model's parameters from the seed's key, as float32 arrays
    holding bf16 values."""
    d, f, L, Le = c["d_model"], c["d_ff"], c["n_layers"], c["n_enc_layers"]
    qd = c["n_heads"] * c["head_dim"]
    dt = DTYPES[c["dtype"]]
    k_embed, k_pos, k_enc, k_dec, _ = jax.random.split(key, 5)

    def attn(key):
        kq, kk, kv, ko = jax.random.split(key, 4)
        return {"wq": _normal(kq, (d, qd), d ** -0.5, dt),
                "wk": _normal(kk, (d, qd), d ** -0.5, dt),
                "wv": _normal(kv, (d, qd), d ** -0.5, dt),
                "wo": _normal(ko, (qd, d), qd ** -0.5 / math.sqrt(2 * L), dt)}

    def mlp(key):
        ki, kd = jax.random.split(key, 2)
        return {"wi": _normal(ki, (d, f), d ** -0.5, dt),
                "wd": _normal(kd, (f, d), f ** -0.5 / math.sqrt(2 * L), dt)}

    def ln():
        return {"g": jnp.ones((d,), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}

    def enc_layer(key):
        ka, km = jax.random.split(key)
        return {"ln1": ln(), "attn": attn(ka), "ln2": ln(), "mlp": mlp(km)}

    def dec_layer(key):
        ka, kc, km = jax.random.split(key, 3)
        return {"ln1": ln(), "self_attn": attn(ka), "ln2": ln(),
                "cross_attn": attn(kc), "ln3": ln(), "mlp": mlp(km)}

    stack = lambda fn, keys: jax.tree.map(  # noqa: E731
        lambda *xs: jnp.stack(xs), *[fn(k) for k in keys])
    return {
        "embed": _normal(k_embed, (c["vocab"], d), 0.02, dt),
        "dec_pos": _normal(k_pos, (c["pos_table"], d), 0.02, dt),
        "enc_layers": stack(enc_layer, jax.random.split(k_enc, Le)),
        "dec_layers": stack(dec_layer, jax.random.split(k_dec, L)),
        "enc_norm": ln(),
        "final_norm": ln(),
    }


def layernorm(p, x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, -1, keepdims=True)
    return xc * lax.rsqrt(var + eps) * p["g"] + p["b"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def sinusoid(S: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    ang = pos / 10_000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pe = jnp.stack([jnp.sin(ang), jnp.cos(ang)], -1).reshape(S, d)
    return pe.astype(dtype).astype(jnp.float32)


def attention(p, x, kv, causal, H, precision):
    B, S, _ = x.shape
    Sk = kv.shape[1]
    hd = p["wq"].shape[1] // H
    q = matmul(x, p["wq"], precision).reshape(B, S, H, hd)
    k = matmul(kv, p["wk"], precision).reshape(B, Sk, H, hd)
    v = matmul(kv, p["wv"], precision).reshape(B, Sk, H, hd)
    s = _einsum("bqhd,bkhd->bhqk", q, k, precision, -1, -1) * hd ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, Sk), bool)), s, -jnp.inf)
    o = _einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision,
                -1, 1)
    return matmul(o.reshape(B, S, H * hd), p["wo"], precision)


def loss_fn(params, tokens, labels, frames, c: Dict, precision="f32"):
    H, dt = c["n_heads"], DTYPES[c["dtype"]]
    B, S = tokens.shape

    def mlp(p, x):
        return matmul(gelu(matmul(x, p["wi"], precision)), p["wd"], precision)

    @jax.checkpoint
    def enc_block(h, lp):
        h = h + attention(lp["attn"], layernorm(lp["ln1"], h),
                          layernorm(lp["ln1"], h), False, H, precision)
        return h + mlp(lp["mlp"], layernorm(lp["ln2"], h)), None

    @jax.checkpoint
    def dec_block(carry, lp):
        h, enc = carry
        x = layernorm(lp["ln1"], h)
        h = h + attention(lp["self_attn"], x, x, True, H, precision)
        h = h + attention(lp["cross_attn"], layernorm(lp["ln2"], h), enc,
                          False, H, precision)
        h = h + mlp(lp["mlp"], layernorm(lp["ln3"], h))
        return (h, enc), None

    # the configuration feeds bf16 frames; positions in the stored dtype
    x = frames.astype(dt).astype(jnp.float32) + sinusoid(
        frames.shape[1], frames.shape[2], dt)
    enc, _ = lax.scan(enc_block, x, params["enc_layers"])
    enc = layernorm(params["enc_norm"], enc)
    h = params["embed"][tokens] + params["dec_pos"][:S][None]
    (h, _), _ = lax.scan(dec_block, (h, enc), params["dec_layers"])
    h = layernorm(params["final_norm"], h)
    logits = matmul(h, params["embed"].T, precision)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


def lr_at(step, o: Dict):
    """Linear warm-up, then cosine decay to ``min_lr_frac`` of the peak."""
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * frac


def adamw(params, grads, m, v, step, o: Dict, dt):
    """AdamW after clipping the global norm; decay on every leaf of two or
    more dimensions. Parameters are stored back in the configuration's
    dtype. Returns the new (params, m, v) and the clipped gradient."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / (norm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(step, o)
    b1, b2 = o["b1"], o["b2"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        wd = o["weight_decay"] if p.ndim >= 2 else 0.0
        u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + o["eps"]) + wd * p
        return (p - lr * u).astype(dt).astype(jnp.float32), m2, v2

    flat_p, tree = jax.tree.flatten(params)
    out = [upd(*a) for a in zip(flat_p, tree.flatten_up_to(grads),
                                tree.flatten_up_to(m), tree.flatten_up_to(v))]
    return tuple(tree.unflatten([o_[i] for o_ in out]) for i in range(3)) \
        + (grads,)


@functools.partial(jax.jit, static_argnames=("c",))
def _init(key, c):
    return init_params(key, dict(c))


@functools.partial(jax.jit, static_argnames=("c", "o", "precision"))
def _step(params, m, v, tokens, labels, frames, step_no, c, o, precision):
    cd, od = dict(c), dict(o)
    loss, grads = jax.value_and_grad(loss_fn)(
        params, tokens, labels, frames, cd, precision)
    return (loss,) + adamw(params, grads, m, v, step_no, od, DTYPES[cd["dtype"]])


def _frozen(d: Dict) -> Tuple:
    return tuple(sorted(d.items()))


def train_steps(seed: int, c: Dict, o: Dict, batches, frames,
                precision: str = "f32") -> Dict:
    """Follow the program's first ``len(batches)`` steps. Returns the
    losses, per-leaf norms of the clipped first gradient and of the
    change of the parameters over all the steps, leaves in the order the
    flattened tree gives them."""
    c, o = _frozen(c), _frozen(o)
    params = _init(jax.random.PRNGKey(seed), c=c)
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, b in enumerate(batches, start=1):
        loss, params, m, v, clipped = _step(
            params, m, v, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
            frames, jnp.float32(i), c=c, o=o, precision=precision)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(clipped)
        del clipped
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return {"losses": losses, "grad": first_grad, "change": change}


def leaf_norms(tree) -> Tuple[float, ...]:
    return tuple(float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
                 for x in jax.tree.leaves(tree))
