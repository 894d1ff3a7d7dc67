"""Flash attention for TPU in Pallas — the attention hot-spot kernel.

Online-softmax tiling with VMEM scratch accumulators, causal block
skipping, and GQA-aware KV indexing. Following the paper's bulk-load
principle, both the K and V tiles for a grid step are read from their refs
*before* any compute (the scores matmul), front-loading the HBM→VMEM
traffic of each step.

Grid: (batch*heads, q_blocks, kv_blocks) with kv innermost so the (m, l,
acc) scratch carries across the kv sweep of one q tile.

Tiles: unless the caller passes them, ``q_block = kv_block =``
:func:`attention_tiles` of the sequence length: the largest of 512, 256
and 128 that divides S, or S itself when S <= 128. A fixed 128 tile
spends most of each grid step on per-step overhead rather than MXU work
and re-reads K/V from HBM once per q block; the larger tile cuts both.

Validated against :func:`repro.kernels.ref.attention_ref` in interpret
mode (CPU) over shape/dtype sweeps; on TPU the same kernel compiles with
MXU-aligned tiles (q_block × head_dim multiples of (8, 128)).

Differentiable: the kernel also writes each row's log-sum-exp, and the
custom VJP runs :func:`blocked_attention_bwd`, the flash-2 backward in
jnp that the blocked CPU path (``models.layers``) shares. The backward
tiles its block scan with the forward's tiles.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.telemetry import telemetry

NEG_INF = -1e30
TILE_SIZES = (512, 256, 128)


def attention_tiles(S: int) -> int:
    """The square flash tile for a sequence of ``S``: the largest of
    :data:`TILE_SIZES` that divides S, or S itself when S <= 128."""
    if S <= TILE_SIZES[-1]:
        return S
    for t in TILE_SIZES:
        if S % t == 0:
            return t
    raise ValueError(f"flash attention needs S <= 128 or a multiple of "
                     f"128, got S={S}")


def attention_layout(B: int, H: int, KH: int, S: int, D: int,
                     q_block: int, kv_block: int) -> dict:
    """The launch geometry of :func:`flash_attention`, as data.

    One source of truth shared by the ``pallas_call`` below and the
    static grid verifier (``repro.verify.grid_check`` certifies exactly
    these index maps): per operand a ``(block_shape, array_shape,
    index_map)`` triple over the grid ``(B*H, q_steps, kv_steps)``.

    The output map ignores ``ki`` — every kv step of one (bh, qi) pair
    revisits the same output block, finalized on the last step; the
    verifier's inert-axis analysis proves that a legal revisit, not a
    write-write race. K/V indexing is GQA-aware: ``bh // H`` recovers
    the batch, ``(bh % H) // group`` the kv head."""
    assert H % KH == 0, "query heads must be a multiple of kv heads"
    group = H // KH
    q_steps, kv_steps = S // q_block, S // kv_block

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return (bh // H, (bh % H) // group, ki, 0)

    return {
        "grid": (B * H, q_steps, kv_steps),
        "q": ((1, q_block, D), (B * H, S, D), q_map),
        "k": ((1, 1, kv_block, D), (B, KH, S, D), kv_map),
        "v": ((1, 1, kv_block, D), (B, KH, S, D), kv_map),
        "o": ((1, q_block, D), (B * H, S, D), q_map),
        # per-row log-sum-exp, replicated over 128 lanes (the backward's
        # residual)
        "lse": ((1, q_block, 128), (B * H, S, 128), q_map),
        # m/l accumulators (q_block, 128) + the (q_block, D) f32 acc
        "scratch_bytes": (q_block * 128 * 2 + q_block * D) * 4,
    }


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                 acc_scr, *, scale: float, causal: bool, q_block: int,
                 kv_block: int, kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step():
        # bulk load: all VMEM reads of this step issued before any compute
        q = q_ref[0, ...]                    # (q_block, d)
        k = k_ref[0, 0, ...]                 # (kv_block, d)
        v = v_ref[0, 0, ...]                 # (kv_block, d)
        m_prev = m_scr[...]                  # (q_block, 128) replicated
        l_prev = l_scr[...]
        acc_prev = acc_scr[...]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (q_blk, kv_blk)
        if causal:
            q_pos = qi * q_block + lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 0)
            k_pos = ki * kv_block + lax.broadcasted_iota(
                jnp.int32, (q_block, kv_block), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_cur = jnp.max(s, axis=-1, keepdims=True)          # (q_blk, 1)
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        alpha = jnp.exp(m_prev[:, :1] - m_new)              # rescale old
        p = jnp.exp(s - m_new)                              # (q_blk, kv_blk)
        l_new = alpha * l_prev[:, :1] + jnp.sum(p, -1, keepdims=True)
        acc = alpha * acc_prev + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc

    if causal:
        # skip fully-masked blocks (query tile entirely above kv tile)
        pl.when((qi + 1) * q_block > ki * kv_block)(_step)
    else:
        _step()

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l_all = l_scr[...]
        l_all = jnp.where(l_all == 0.0, 1.0, l_all)
        o_ref[0, ...] = (acc_scr[...] / l_all[:, :1]).astype(o_ref.dtype)
        lse_ref[0, ...] = m_scr[...] + jnp.log(l_all)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    q_block: Optional[int] = None,
                    kv_block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q:(B,H,S,D) k/v:(B,KH,S,D) → (B,H,S,D). GQA when KH < H.
    Interpret mode only on the CPU backend.

    A tile left as ``None`` is :func:`attention_tiles` of S; an explicit
    tile wins (capped at S). The backward runs with the same tiles. Each
    trace records its geometry in ``telemetry().snapshot()
    ["flash_tiles"]``."""
    S, D = q.shape[2], q.shape[3]
    scale = (D ** -0.5) if scale is None else scale
    interpret = (jax.default_backend() == "cpu") if interpret is None \
        else interpret
    q_block = min(q_block or attention_tiles(S), S)
    kv_block = min(kv_block or attention_tiles(S), S)
    assert S % q_block == 0 and S % kv_block == 0
    telemetry().record_flash_tiles(q.shape, q_block, kv_block)
    return _flash(q, k, v, causal, scale, q_block, kv_block, interpret)


def _flash_call(q, k, v, causal, scale, q_block, kv_block, interpret):
    """The Pallas launch: (o (B,H,S,D), lse (B,H,S) f32)."""
    B, H, S, D = q.shape
    KH = k.shape[1]
    q3 = q.reshape(B * H, S, D)
    lay = attention_layout(B, H, KH, S, D, q_block, kv_block)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, q_block=q_block,
        kv_block=kv_block, kv_steps=S // kv_block)
    o, lse = pl.pallas_call(
        kernel,
        grid=lay["grid"],
        in_specs=[pl.BlockSpec(lay[n][0], lay[n][2])
                  for n in ("q", "k", "v")],
        out_specs=[pl.BlockSpec(lay[n][0], lay[n][2]) for n in ("o", "lse")],
        out_shape=[jax.ShapeDtypeStruct(lay["o"][1], q.dtype),
                   jax.ShapeDtypeStruct(lay["lse"][1], jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q3, k, v)
    return o.reshape(B, H, S, D), lse[..., 0].reshape(B, H, S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, q_block, kv_block, interpret):
    return _flash_call(q, k, v, causal, scale, q_block, kv_block,
                       interpret)[0]


def _flash_fwd(q, k, v, causal, scale, q_block, kv_block, interpret):
    o, lse = _flash_call(q, k, v, causal, scale, q_block, kv_block,
                         interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, q_block, kv_block, interpret, res, do):
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    KH = k.shape[1]
    rep = H // KH
    kr = jnp.repeat(k, rep, axis=1) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=1) if rep > 1 else v
    dq, dk, dv = blocked_attention_bwd(causal, scale, q_block, kv_block,
                                       (q, kr, vr, o, lse), do)
    if rep > 1:   # fold the query-head groups back onto their kv head
        dk = dk.reshape(B, KH, rep, S, D).sum(2, dtype=jnp.float32)
        dv = dv.reshape(B, KH, rep, S, D).sum(2, dtype=jnp.float32)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def block_ids(q_block: int, kv_block: int):
    """Row/column position iotas of one (q_block, kv_block) score tile."""
    qpos = lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
    kpos = lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
    return qpos, kpos


def blocked_attention_bwd(causal, scale, q_block, kv_block, res, do):
    """Flash-2 backward in jnp over (q_block, kv_block) tiles: recomputes
    each block's probabilities from the saved log-sum-exp instead of
    keeping the (S, S) matrix. ``res = (q, k, v, o, lse)`` with k/v at
    the query head count; returns (dq, dk, dv)."""
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    nq, nk = S // q_block, S // kv_block
    qb = q.reshape(B, H, nq, q_block, D).transpose(2, 0, 1, 3, 4)
    kb = k.reshape(B, H, nk, kv_block, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nk, kv_block, D).transpose(2, 0, 1, 3, 4)
    dob = do.reshape(B, H, nq, q_block, D).transpose(2, 0, 1, 3, 4)
    lseb = lse.reshape(B, H, nq, q_block).transpose(2, 0, 1, 3)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    deltab = delta.reshape(B, H, nq, q_block).transpose(2, 0, 1, 3)
    qpos0, kpos0 = block_ids(q_block, kv_block)

    def kv_outer(_, ki_and_kv):
        ki, kt, vt = ki_and_kv

        def q_inner(carry, qi_pack):
            dk_a, dv_a = carry
            qi, qt, dot_, lse_i, delta_i = qi_pack
            s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                msk = (qi * q_block + qpos0) >= (ki * kv_block + kpos0)
                s = jnp.where(msk[None, None], s, -1e30)
            pmat = jnp.exp(s - lse_i[..., None])
            dp = jnp.einsum("bhqd,bhkd->bhqk", dot_.astype(jnp.float32),
                            vt.astype(jnp.float32))
            ds = pmat * (dp - delta_i[..., None]) * scale
            dk_a = dk_a + jnp.einsum("bhqk,bhqd->bhkd", ds,
                                     qt.astype(jnp.float32))
            dv_a = dv_a + jnp.einsum("bhqk,bhqd->bhkd", pmat,
                                     dot_.astype(jnp.float32))
            dq_i = jnp.einsum("bhqk,bhkd->bhqd", ds, kt.astype(jnp.float32))
            return (dk_a, dv_a), dq_i

        B_, H_ = kt.shape[0], kt.shape[1]
        init = (jnp.zeros((B_, H_, kv_block, D), jnp.float32),
                jnp.zeros((B_, H_, kv_block, D), jnp.float32))
        (dk_b, dv_b), dq_parts = lax.scan(
            q_inner, init, (jnp.arange(nq), qb, dob, lseb, deltab))
        return None, (dk_b, dv_b, dq_parts)

    _, (dk_b, dv_b, dq_all) = lax.scan(kv_outer, None,
                                       (jnp.arange(nk), kb, vb))
    dq = dq_all.sum(0).transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)
    dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)
    dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def decode_attention(q, k_cache, v_cache, *, scale: Optional[float] = None):
    """Single-token decode: q:(B,H,1,D) against k/v:(B,KH,S,D). Pure jnp —
    a GEMV-shaped op; GQA handled by grouped einsums (the repeated-KV
    materialization would dominate decode memory at 32k context)."""
    B, H, Q, D = q.shape
    KH = k_cache.shape[1]
    rep = H // KH
    scale = (D ** -0.5) if scale is None else scale
    qg = q.reshape(B, KH, rep, Q, D)
    logits = jnp.einsum("bkgqd,bksd->bkgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", probs.astype(q.dtype), v_cache)
    return o.reshape(B, H, Q, D)
