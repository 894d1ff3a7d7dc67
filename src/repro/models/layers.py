"""Model building blocks: attention (GQA/M-RoPE), MLP, MoE, Mamba2 SSD.

Every elementwise hot-spot routes through the saturated kernels in
:mod:`repro.kernels.ops`; matmuls stay as einsums (MXU territory the
saturator deliberately leaves alone, exactly as the paper leaves loop
structure to the compiler).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.kernels.flash_attention import block_ids, blocked_attention_bwd
from repro.parallel import ctx
from .common import ModelConfig, dense_init, rope_cos_sin, split_keys


def _tp_size() -> int:
    mesh = ctx.active_mesh()
    return 1 if mesh is None else mesh.shape.get("model", 1)


def _pad_heads_kv(k, v, H: int, Hp: int):
    """Repeat GQA KV to full (padded) head count locally: KV is replicated
    over the model axis (wk/wv are row-replicated), so the repeat+pad is a
    local slice-free broadcast; the subsequent head-shard constraint is a
    free local slice."""
    KH = k.shape[1]
    if KH != H:
        rep = H // KH
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if Hp != H:
        pad = ((0, 0), (0, Hp - H), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    k = ctx.constrain(k, "dp", "tp", None, None)
    v = ctx.constrain(v, "dp", "tp", None, None)
    return k, v


def _padded_H(cfg) -> int:
    """Attention heads padded to the model axis (bounded ≤4/3 compute on
    the attention core; avoids mid-head SPMD shardings whose per-block
    collectives measured 20s+/step on minitron — see EXPERIMENTS.md §Perf).
    The padding lives in the WEIGHTS (zero wq columns / zero wo rows), so
    results are exact and no activation pad/slice resharding appears."""
    tp = _tp_size()
    return ((cfg.n_heads + tp - 1) // tp) * tp


def _wq_padded(p, cfg, Hp):
    if Hp == cfg.n_heads:
        return p["wq"], p["wo"]
    extra = (Hp - cfg.n_heads) * cfg.head_dim
    wq = jnp.pad(p["wq"], ((0, 0), (0, extra)))
    wo = jnp.pad(p["wo"], ((0, extra), (0, 0)))
    return wq, wo



# ---------------------------------------------------------------------------
# blocked attention (pure jnp, memory-bounded): the CPU/dry-run path.
# Same online-softmax math as the Pallas flash kernel; flash-2 style
# custom VJP (shared with the kernel: blocked_attention_bwd) recomputes
# block scores instead of saving (S x S) probs.
# ---------------------------------------------------------------------------
def blocked_attention(q, k, v, *, causal=True, scale=None,
                      q_block=512, kv_block=512):
    B, H, S, D = q.shape
    KH = k.shape[1]
    if KH != H:
        rep = H // KH
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = (D ** -0.5) if scale is None else scale
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    if S % q_block or S % kv_block:
        return _naive(q, k, v, causal, scale)
    return _flash(q, k, v, causal, scale, q_block, kv_block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, q_block, kv_block):
    o, _ = _flash_fwd_impl(q, k, v, causal, scale, q_block, kv_block)
    return o


def _flash_fwd_impl(q, k, v, causal, scale, q_block, kv_block):
    B, H, S, D = q.shape
    nq, nk = S // q_block, S // kv_block
    qb = q.reshape(B, H, nq, q_block, D).transpose(2, 0, 1, 3, 4)
    kb = k.reshape(B, H, nk, kv_block, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nk, kv_block, D).transpose(2, 0, 1, 3, 4)
    qpos0, kpos0 = block_ids(q_block, kv_block)

    def q_step(_, qi_and_q):
        qi, qt = qi_and_q

        def kv_step(carry, ki_and_kv):
            m_p, l_p, acc = carry
            ki, kt, vt = ki_and_kv
            s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                msk = (qi * q_block + qpos0) >= (ki * kv_block + kpos0)
                s = jnp.where(msk[None, None], s, -1e30)
            m_c = jnp.max(s, -1, keepdims=True)
            m_n = jnp.maximum(m_p, m_c)
            alpha = jnp.exp(m_p - m_n)
            pmat = jnp.exp(s - m_n)
            l_n = alpha * l_p + pmat.sum(-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                "bhqk,bhkd->bhqd", pmat.astype(vt.dtype), vt,
                preferred_element_type=jnp.float32)
            return (m_n, l_n, acc), None

        B_, H_ = qt.shape[0], qt.shape[1]
        init = (jnp.full((B_, H_, q_block, 1), -1e30, jnp.float32),
                jnp.zeros((B_, H_, q_block, 1), jnp.float32),
                jnp.zeros((B_, H_, q_block, D), jnp.float32))
        (m_f, l_f, acc), _ = lax.scan(kv_step, init,
                                      (jnp.arange(nk), kb, vb))
        l_safe = jnp.where(l_f == 0, 1.0, l_f)
        o = (acc / l_safe).astype(qt.dtype)
        lse = (m_f + jnp.log(l_safe))[..., 0]          # (B,H,qb)
        return None, (o, lse)

    _, (ob, lseb) = lax.scan(q_step, None, (jnp.arange(nq), qb))
    o = ob.transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)
    lse = lseb.transpose(1, 2, 0, 3).reshape(B, H, S)
    return o, lse


def _flash_fwd(q, k, v, causal, scale, q_block, kv_block):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, q_block, kv_block)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, blocked_attention_bwd)


def _naive(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    pmat = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", pmat.astype(q.dtype), v)


def full_attention(q, k, v, *, causal=True, scale=None):
    """Dispatch: Pallas flash on TPU, blocked jnp elsewhere."""
    if ops.current_impl() == "pallas":
        return ops.attention(q, k, v, causal=causal, scale=scale)
    return blocked_attention(q, k, v, causal=causal, scale=scale)


# ---------------------------------------------------------------------------
# Attention block (GQA + RoPE), with prefill/decode cache paths
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    ks = split_keys(key, ["wq", "wk", "wv", "wo"])
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return {
        "wq": dense_init(ks["wq"], (d, qd), cfg.dtype),
        "wk": dense_init(ks["wk"], (d, kvd), cfg.dtype),
        "wv": dense_init(ks["wv"], (d, kvd), cfg.dtype),
        "wo": dense_init(ks["wo"], (qd, d), cfg.dtype,
                         scale=(qd ** -0.5) / math.sqrt(2 * cfg.n_layers)),
    }


def _split_heads(x, n_heads, head_dim):
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def attn_apply(p, x, cos, sin, cfg: ModelConfig, *, causal=True,
               kv_x: Optional[jnp.ndarray] = None):
    """Full-sequence attention. kv_x (encoder states) enables cross-attn."""
    src = x if kv_x is None else kv_x
    Hp = _padded_H(cfg)
    wq, wo = _wq_padded(p, cfg, Hp)
    q = _split_heads(x @ wq, Hp, cfg.head_dim)
    k = _split_heads(src @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(src @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cos is not None and kv_x is None:
        q = ops.rotary(q, cos[:, None], sin[:, None]).astype(x.dtype)
        k = ops.rotary(k, cos[:, None], sin[:, None]).astype(x.dtype)
    q = ctx.constrain(q, "dp", "tp", None, None)
    k, v = _pad_heads_kv(k, v, cfg.n_heads, Hp)
    o = full_attention(q, k, v, causal=causal and kv_x is None)
    return _merge_heads(o) @ wo


def attn_prefill(p, x, cos, sin, cfg: ModelConfig):
    """Returns (out, (k_cache, v_cache)) for subsequent decode."""
    Hp = _padded_H(cfg)
    wq, wo = _wq_padded(p, cfg, Hp)
    q = _split_heads(x @ wq, Hp, cfg.head_dim)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cos is not None:
        q = ops.rotary(q, cos[:, None], sin[:, None]).astype(x.dtype)
        k = ops.rotary(k, cos[:, None], sin[:, None]).astype(x.dtype)
    kv_cache = (k, v)
    q = ctx.constrain(q, "dp", "tp", None, None)
    kp, vp = _pad_heads_kv(k, v, cfg.n_heads, Hp)
    o = full_attention(q, kp, vp, causal=True)
    return _merge_heads(o) @ wo, kv_cache


def attn_decode(p, x1, kv_cache, pos, cfg: ModelConfig,
                cos1=None, sin1=None):
    """One-token decode. x1:(B,1,D); kv_cache: (k,v) each (B,KH,S,hd);
    pos: () current position. Cache updated in place at pos."""
    k_c, v_c = kv_cache
    q = _split_heads(x1 @ p["wq"], cfg.n_heads, cfg.head_dim)
    k1 = _split_heads(x1 @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v1 = _split_heads(x1 @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cos1 is not None:
        q = ops.rotary(q, cos1[:, None], sin1[:, None]).astype(x1.dtype)
        k1 = ops.rotary(k1, cos1[:, None], sin1[:, None]).astype(x1.dtype)
    k_c = lax.dynamic_update_slice(k_c, k1.astype(k_c.dtype),
                                   (0, 0, pos, 0))
    v_c = lax.dynamic_update_slice(v_c, v1.astype(v_c.dtype),
                                   (0, 0, pos, 0))
    S = k_c.shape[2]
    # mask out positions beyond pos
    valid = jnp.arange(S) <= pos
    scale = cfg.head_dim ** -0.5
    KH = cfg.n_kv_heads
    rep = cfg.n_heads // KH
    B = q.shape[0]
    # GQA-grouped einsum: never materialize the head-repeated KV cache
    # (for mistral-large decode_32k that repeat was ~100 GB of temps)
    qg = q.reshape(B, KH, rep, 1, cfg.head_dim)
    k_r = k_c.astype(qg.dtype) if k_c.dtype != qg.dtype else k_c
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, k_r,
                        preferred_element_type=jnp.float32) * scale
    # keep the decode logits sharded like the cache (batch×sequence);
    # left unpinned they came back replicated (16 GiB of temps at 32k)
    logits = ctx.constrain(logits, "dp", None, None, None, "tp")
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    # Mirror the flash kernel's order of operations exactly — unnormalized
    # exp weights cast to the value dtype, PV accumulated in f32, then the
    # f32 normalizer applied — so decode reproduces teacher-forcing logits
    # bitwise instead of drifting one bf16 ulp per layer.
    m = jnp.max(logits, -1, keepdims=True)
    pmat = jnp.exp(logits - m)
    l = pmat.sum(-1, keepdims=True)
    v_r = v_c.astype(x1.dtype) if v_c.dtype != x1.dtype else v_c
    acc = jnp.einsum("bkgqs,bksd->bkgqd", pmat.astype(v_r.dtype), v_r,
                     preferred_element_type=jnp.float32)
    o = (acc / l).astype(x1.dtype)
    o = o.reshape(B, cfg.n_heads, 1, cfg.head_dim)
    o = ctx.constrain(o, "dp", None, None, None)
    return _merge_heads(o) @ p["wo"], (k_c, v_c)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    if cfg.act == "swiglu":
        ks = split_keys(key, ["wg", "wu", "wd"])
        return {"wg": dense_init(ks["wg"], (d, d_ff), cfg.dtype),
                "wu": dense_init(ks["wu"], (d, d_ff), cfg.dtype),
                "wd": dense_init(ks["wd"], (d_ff, d), cfg.dtype,
                                 scale=(d_ff ** -0.5)
                                 / math.sqrt(2 * cfg.n_layers))}
    ks = split_keys(key, ["wi", "wd"])
    return {"wi": dense_init(ks["wi"], (d, d_ff), cfg.dtype),
            "wd": dense_init(ks["wd"], (d_ff, d), cfg.dtype,
                             scale=(d_ff ** -0.5)
                             / math.sqrt(2 * cfg.n_layers))}


def mlp_apply(p, x, cfg: ModelConfig):
    if cfg.act == "swiglu":
        return ops.swiglu(x @ p["wg"], x @ p["wu"]) @ p["wd"]
    return ops.gelu(x @ p["wi"]) @ p["wd"]


# ---------------------------------------------------------------------------
# MoE (capacity-based sorted dispatch; EP-shardable over the expert axis)
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig):
    mc = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, mc.n_experts
    names = ["router", "wg", "wu", "wd"]
    if mc.residual_ffn_dim:
        names.append("res")
    ks = split_keys(key, names)
    p = {
        "router": dense_init(ks["router"], (d, e), jnp.float32),
        "wg": dense_init(ks["wg"], (e, d, f), cfg.dtype),
        "wu": dense_init(ks["wu"], (e, d, f), cfg.dtype),
        "wd": dense_init(ks["wd"], (e, f, d), cfg.dtype,
                         scale=(f ** -0.5) / math.sqrt(2 * cfg.n_layers)),
    }
    if mc.residual_ffn_dim:
        rcfg = cfg
        p["res"] = mlp_init(ks["res"], rcfg, d_ff=mc.residual_ffn_dim)
    return p


def moe_apply(p, x, cfg: ModelConfig):
    """Grouped token-capacity MoE (GShard-style, dropless up to the
    capacity factor). Tokens are split into G groups aligned with the
    data-parallel axis so routing gathers stay shard-local; the expert
    einsums are sharded over the expert axis (EP) — the cross-shard
    exchange is the canonical MoE all-to-all, left to SPMD.
    Compute cost ~= top_k x one-expert cost per token."""
    from repro.parallel import ctx
    mc = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = mc.n_experts, mc.top_k
    G = 32
    while T % G:
        G //= 2
    TG = T // G
    C = max(int(math.ceil(TG * K / E * mc.capacity_factor)), 1)
    xf = x.reshape(G, TG, D)
    xf = ctx.constrain(xf, "dp", None, None)
    logits = xf.astype(jnp.float32) @ p["router"]       # (G,TG,E)
    probs = ops.moe_router_probs(logits)                # saturated softmax
    wts, idx = lax.top_k(probs, K)                      # (G,TG,K)
    wts = wts / jnp.maximum(wts.sum(-1, keepdims=True), 1e-9)

    fe = idx.reshape(G, TG * K)                         # expert ids
    order = jnp.argsort(fe, axis=-1)                    # (G,TG*K)
    counts = jax.vmap(lambda f: jnp.bincount(f, length=E))(fe)   # (G,E)
    starts = jnp.cumsum(counts, axis=-1) - counts
    eidx = jnp.repeat(jnp.arange(E), C)                 # (E*C,)
    cpos = jnp.tile(jnp.arange(C), E)
    gpos = starts[:, eidx] + cpos[None]                 # (G,E*C)
    valid = cpos[None] < counts[:, eidx]                # (G,E*C)
    gpos = jnp.where(valid, gpos, 0)
    slot = jnp.take_along_axis(order, gpos, axis=-1)    # (G,E*C) into TG*K
    tok = slot // K                                     # (G,E*C) into TG

    xg = jnp.take_along_axis(
        xf, tok[..., None], axis=1) * valid[..., None].astype(xf.dtype)
    xg = xg.reshape(G, E, C, D)
    xg = ctx.constrain(xg, "dp", "tp", None, None)      # EP dispatch
    h = jnp.einsum("gecd,edf->gecf", xg, p["wg"])
    u = jnp.einsum("gecd,edf->gecf", xg, p["wu"])
    a = ops.swiglu(h, u)
    y = jnp.einsum("gecf,efd->gecd", a, p["wd"])
    y = y.reshape(G, E * C, D)
    w_flat = jnp.take_along_axis(wts.reshape(G, TG * K), slot, axis=-1)
    y = y * (w_flat * valid)[..., None].astype(y.dtype)
    out = jnp.zeros((G, TG, D), x.dtype)
    out = jax.vmap(lambda o, t, yy: o.at[t].add(yy))(out, tok,
                                                     y.astype(x.dtype))
    out = ctx.constrain(out, "dp", None, None)
    # router aux loss (load balancing)
    me = probs.mean((0, 1))                             # (E,)
    ce = counts.sum(0).astype(jnp.float32) / (T * K)
    aux = E * jnp.sum(me * ce)
    if mc.residual_ffn_dim:
        out = out + mlp_apply(p["res"], xf, cfg)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Mamba2 (SSD) block
# ---------------------------------------------------------------------------
def mamba_init(key, cfg: ModelConfig):
    """Separate per-stream projections (z/x/B/C/dt) so tensor parallelism
    can shard the d_inner streams without slicing through a fused dim."""
    sc = cfg.ssm
    d = cfg.d_model
    di = sc.d_inner(d)
    nh = sc.n_heads(d)
    N = sc.state_dim
    ks = split_keys(key, ["z", "x", "B", "C", "dt", "out", "cx", "cb",
                          "cc", "bias"])
    return {
        "w_z": dense_init(ks["z"], (d, di), cfg.dtype),
        "w_x": dense_init(ks["x"], (d, di), cfg.dtype),
        "w_B": dense_init(ks["B"], (d, N), cfg.dtype),
        "w_C": dense_init(ks["C"], (d, N), cfg.dtype),
        "w_dt": dense_init(ks["dt"], (d, nh), cfg.dtype),
        "w_out": dense_init(ks["out"], (di, d), cfg.dtype,
                            scale=(di ** -0.5) / math.sqrt(2 * cfg.n_layers)),
        "conv_x": dense_init(ks["cx"], (sc.conv_width, di), cfg.dtype,
                             scale=0.5),
        "conv_b": dense_init(ks["cb"], (sc.conv_width, N), cfg.dtype,
                             scale=0.5),
        "conv_c": dense_init(ks["cc"], (sc.conv_width, N), cfg.dtype,
                             scale=0.5),
        "a_log": jnp.zeros((nh,), jnp.float32)
        + jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jax.random.uniform(ks["bias"], (nh,), jnp.float32,
                                      -4.0, -1.0),
        "norm_g": jnp.ones((di,), cfg.dtype),
    }


def _causal_conv(u, w):
    """Depthwise causal conv. u:(B,S,Ch) w:(W,Ch)."""
    W = w.shape[0]
    pads = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for t in range(W):
        out = out + pads[:, t:t + u.shape[1]] * w[t]
    return out


def _mamba_proj(p, x, cfg):
    """Input projections: z, xs, b, c, dt_raw (separate streams)."""
    sc = cfg.ssm
    d = cfg.d_model
    di = sc.d_inner(d)
    N = sc.state_dim
    nh = sc.n_heads(d)
    z = x @ p["w_z"]
    xs = x @ p["w_x"]
    b = x @ p["w_B"]
    c = x @ p["w_C"]
    dt_raw = x @ p["w_dt"]
    return z, xs, b, c, dt_raw, di, N, nh


def mamba_apply(p, x, cfg: ModelConfig):
    sc = cfg.ssm
    B, S, _ = x.shape
    z, xs, b, c, dt_raw, di, N, nh = _mamba_proj(p, x, cfg)
    xs = _causal_conv(xs, p["conv_x"])
    b = _causal_conv(b, p["conv_b"])
    c = _causal_conv(c, p["conv_c"])
    xs = xs * lax.logistic(xs)                          # silu
    b = b * lax.logistic(b)
    c = c * lax.logistic(c)
    b_mat = b.astype(jnp.float32)
    c_mat = c.astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"])                # (B,S,nh)
    y = ops.ssd(xs.reshape(B, S, nh, sc.head_dim).astype(jnp.float32),
                dt, p["a_log"], b_mat, c_mat, p["d_skip"],
                chunk=sc.chunk)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = ops.rmsnorm_gated(y, z, p["norm_g"])
    return y @ p["w_out"]


def mamba_init_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    sc = cfg.ssm
    d = cfg.d_model
    di = sc.d_inner(d)
    nh = sc.n_heads(d)
    return {
        "h": jnp.zeros((batch, nh, sc.state_dim, sc.head_dim), jnp.float32),
        "conv_x": jnp.zeros((batch, sc.conv_width - 1, di), dtype),
        "conv_b": jnp.zeros((batch, sc.conv_width - 1, sc.state_dim), dtype),
        "conv_c": jnp.zeros((batch, sc.conv_width - 1, sc.state_dim), dtype),
    }


def mamba_decode(p, x1, state, cfg: ModelConfig):
    """One-token recurrent step. x1:(B,1,D); state from mamba_init_state."""
    sc = cfg.ssm
    B = x1.shape[0]
    z, xs, b, c, dt_raw, di, N, nh = _mamba_proj(p, x1, cfg)

    def conv_step(hist, new, w):
        hist = jnp.concatenate([hist, new], axis=1)       # (B,W,Ch)
        out = jnp.einsum("bwc,wc->bc", hist, w)[:, None]
        return out * lax.logistic(out), hist[:, 1:]

    xs_c, cx = conv_step(state["conv_x"], xs, p["conv_x"])
    b_c, cb = conv_step(state["conv_b"], b, p["conv_b"])
    c_c, cc = conv_step(state["conv_c"], c, p["conv_c"])
    b_t = b_c[:, 0].astype(jnp.float32)
    c_t = c_c[:, 0].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    h, y = ops.ssd_decode(state["h"],
                          xs_c[:, 0].reshape(B, nh, sc.head_dim)
                          .astype(jnp.float32),
                          dt, p["a_log"], b_t, c_t, p["d_skip"])
    y = y.reshape(B, 1, di).astype(x1.dtype)
    y = ops.rmsnorm_gated(y, z, p["norm_g"])
    new_state = {"h": h, "conv_x": cx, "conv_b": cb, "conv_c": cc}
    return y @ p["w_out"], new_state


# ---------------------------------------------------------------------------
# Norm dispatcher
# ---------------------------------------------------------------------------
def norm_init(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"g": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)}
    return {"g": jnp.ones((d,), cfg.dtype)}


def norm_apply(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        out = ops.layernorm(xf, p["g"].astype(jnp.float32),
                            p["b"].astype(jnp.float32))
    else:
        out = ops.rmsnorm(xf, p["g"].astype(jnp.float32))
    return out.astype(x.dtype)
