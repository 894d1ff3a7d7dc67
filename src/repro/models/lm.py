"""Decoder-only language models: dense GQA, MoE, Mamba2 SSD, Zamba2 hybrid,
and Qwen2-VL text backbone (M-RoPE). One scan-compiled layer stack per
family — 88-layer configs compile one layer body.

Uniform API (used by launch/train.py, launch/serve.py, launch/dryrun.py):
  init(rng) -> params
  loss(params, batch) -> scalar            batch: tokens/labels[/positions]
  prefill(params, tokens) -> (logits, cache)
  init_cache(batch, seq) -> cache          (decode dry-run entry)
  decode_step(params, cache, token) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.kernels.ssd_scan import ssd_scan_jnp
from repro.parallel import ctx
from .common import (ModelConfig, chunked_softmax_xent, dense_init,
                     mrope_cos_sin, rope_cos_sin, split_keys)
from . import layers as L


def _stacked_init(layer_init_fn, key, n_layers):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(layer_init_fn)(keys)


class LM:
    """Decoder-only LM. Family-specific blocks, shared skeleton."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.family in ("dense", "moe", "ssm", "hybrid", "vlm")
        self.cfg = cfg

    # -- parameters ------------------------------------------------------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        ks = split_keys(rng, ["embed", "unembed", "layers", "shared",
                              "final"])
        params: Dict[str, Any] = {
            "embed": dense_init(ks["embed"], (cfg.vocab, cfg.d_model),
                                cfg.dtype, scale=0.02),
            "final_norm": L.norm_init(cfg),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(
                ks["unembed"], (cfg.d_model, cfg.vocab), cfg.dtype)
        params["layers"] = _stacked_init(
            lambda k: self._layer_init(k), ks["layers"], cfg.n_layers)
        if cfg.family == "hybrid":
            params["shared"] = self._shared_block_init(ks["shared"])
        return params

    def _layer_init(self, key):
        cfg = self.cfg
        if cfg.family in ("dense", "vlm"):
            ka, km = jax.random.split(key)
            return {"ln1": L.norm_init(cfg), "attn": L.attn_init(ka, cfg),
                    "ln2": L.norm_init(cfg), "mlp": L.mlp_init(km, cfg)}
        if cfg.family == "moe":
            ka, km = jax.random.split(key)
            return {"ln1": L.norm_init(cfg), "attn": L.attn_init(ka, cfg),
                    "ln2": L.norm_init(cfg), "moe": L.moe_init(km, cfg)}
        # ssm / hybrid: pure mamba2 block
        return {"ln1": L.norm_init(cfg), "mamba": L.mamba_init(key, cfg)}

    def _shared_block_init(self, key):
        cfg = self.cfg
        ka, km = jax.random.split(key)
        return {"ln1": L.norm_init(cfg), "attn": L.attn_init(ka, cfg),
                "ln2": L.norm_init(cfg), "mlp": L.mlp_init(km, cfg)}

    # -- rope ---------------------------------------------------------------------
    def _cos_sin(self, positions, batch_positions=None):
        cfg = self.cfg
        if cfg.family == "vlm":
            pos3 = batch_positions
            if pos3 is None:
                pos3 = jnp.broadcast_to(positions[None, None, :],
                                        (3, 1, positions.shape[-1]))
            return mrope_cos_sin(pos3, cfg.head_dim, cfg.rope_theta,
                                 cfg.mrope_sections)
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        return cos[None], sin[None]       # (1, S, hd)

    # -- forward (full sequence) ------------------------------------------------------
    def _layer_apply(self, p, h, cos, sin):
        cfg = self.cfg
        if cfg.family in ("dense", "vlm"):
            h = h + L.attn_apply(p["attn"], L.norm_apply(p["ln1"], h, cfg),
                                 cos, sin, cfg)
            h = h + L.mlp_apply(p["mlp"], L.norm_apply(p["ln2"], h, cfg), cfg)
            return h, jnp.float32(0.0)
        if cfg.family == "moe":
            h = h + L.attn_apply(p["attn"], L.norm_apply(p["ln1"], h, cfg),
                                 cos, sin, cfg)
            y, aux = L.moe_apply(p["moe"], L.norm_apply(p["ln2"], h, cfg), cfg)
            return h + y, aux
        # ssm / hybrid
        h = h + L.mamba_apply(p["mamba"], L.norm_apply(p["ln1"], h, cfg), cfg)
        return h, jnp.float32(0.0)

    def forward(self, params, tokens, positions3=None):
        """tokens (B, S) -> final hidden (B, S, D), aux loss."""
        cfg = self.cfg
        B, S = tokens.shape
        h = ctx.constrain(params["embed"][tokens], "dp", None, None)
        pos = jnp.arange(S)
        cos, sin = self._cos_sin(pos, positions3)

        seq_ax = "tp" if cfg.seq_shard else None

        def body(h, lp):
            out, aux = self._layer_apply(lp, h, cos, sin)
            return ctx.constrain(out, "dp", seq_ax, None), aux

        step = jax.checkpoint(body) if cfg.remat else body

        if cfg.family == "hybrid" and cfg.shared_attn_every:
            k = cfg.shared_attn_every
            n_out = cfg.n_layers // k
            grouped = jax.tree.map(
                lambda a: a.reshape((n_out, k) + a.shape[1:]),
                params["layers"])
            shared = params["shared"]

            def outer(h, gp):
                h, auxs = lax.scan(step, h, gp)
                h = h + L.attn_apply(
                    shared["attn"], L.norm_apply(shared["ln1"], h, cfg),
                    cos, sin, cfg)
                h = h + L.mlp_apply(
                    shared["mlp"], L.norm_apply(shared["ln2"], h, cfg), cfg)
                return h, auxs.sum()

            outer_step = jax.checkpoint(outer) if cfg.remat else outer
            h, auxs = lax.scan(outer_step, h, grouped)
        else:
            h, auxs = lax.scan(step, h, params["layers"])
        h = L.norm_apply(params["final_norm"], h, cfg)
        return h, auxs.sum()

    def _unembed(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["unembed"]

    def loss(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        tokens = batch["tokens"]
        labels = batch["labels"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(labels, jnp.float32)
        h, aux = self.forward(params, tokens, batch.get("positions"))
        xent = chunked_softmax_xent(h, self._unembed(params), labels, mask,
                                    chunk=cfg.loss_chunk)
        return xent + 0.01 * aux

    def logits(self, params, tokens, positions3=None):
        h, _ = self.forward(params, tokens, positions3)
        return h.astype(jnp.float32) @ self._unembed(params).astype(
            jnp.float32)

    # -- caches ------------------------------------------------------------------------
    def _cache_dtype(self):
        return jnp.float8_e4m3fn if self.cfg.kv_cache_dtype == "f8" \
            else self.cfg.dtype

    def init_cache(self, batch: int, max_seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        Lc = cfg.n_layers
        cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
        if cfg.family in ("dense", "moe", "vlm"):
            cache["k"] = jnp.zeros((Lc, batch, cfg.n_kv_heads, max_seq,
                                    cfg.head_dim), self._cache_dtype())
            cache["v"] = jnp.zeros_like(cache["k"])
        elif cfg.family == "ssm":
            st = L.mamba_init_state(cfg, batch, cfg.dtype)
            cache["ssm"] = jax.tree.map(
                lambda a: jnp.zeros((Lc,) + a.shape, a.dtype), st)
        elif cfg.family == "hybrid":
            st = L.mamba_init_state(cfg, batch, cfg.dtype)
            cache["ssm"] = jax.tree.map(
                lambda a: jnp.zeros((Lc,) + a.shape, a.dtype), st)
            n_shared = cfg.n_layers // cfg.shared_attn_every
            cache["k"] = jnp.zeros((n_shared, batch, cfg.n_kv_heads,
                                    max_seq, cfg.head_dim),
                                   self._cache_dtype())
            cache["v"] = jnp.zeros_like(cache["k"])
        return cache

    # -- prefill ---------------------------------------------------------------------------
    def prefill(self, params, tokens, positions3=None,
                max_seq: Optional[int] = None):
        """Full-sequence pass building a decode cache; returns last logits.
        ``max_seq`` reserves cache room for decode growth (default S+256)."""
        cfg = self.cfg
        B, S = tokens.shape
        max_seq = max_seq or (S + 256)
        assert max_seq >= S
        h = params["embed"][tokens]
        pos = jnp.arange(S)
        cos, sin = self._cos_sin(pos, positions3)
        cache = self.init_cache(B, max_seq)
        cache["pos"] = jnp.int32(S)

        if cfg.family in ("dense", "moe", "vlm"):
            def body(h, lp):
                xn = L.norm_apply(lp["ln1"], h, cfg)
                a, kv = L.attn_prefill(lp["attn"], xn, cos, sin, cfg)
                h = h + a
                if cfg.family == "moe":
                    y, _ = L.moe_apply(lp["moe"],
                                       L.norm_apply(lp["ln2"], h, cfg), cfg)
                else:
                    y = L.mlp_apply(lp["mlp"],
                                    L.norm_apply(lp["ln2"], h, cfg), cfg)
                return h + y, kv
            h, kvs = lax.scan(body, h, params["layers"])
            cdt = self._cache_dtype()
            cache["k"], cache["v"] = jax.tree.map(
                lambda a: jnp.pad(a.astype(cdt),
                                  ((0, 0), (0, 0), (0, 0),
                                   (0, max_seq - S), (0, 0))), kvs)
        elif cfg.family in ("ssm", "hybrid"):
            h, cache = self._prefill_ssm(params, h, cos, sin, cache)
        h = L.norm_apply(params["final_norm"], h, cfg)
        logits = (h[:, -1:].astype(jnp.float32)
                  @ self._unembed(params).astype(jnp.float32))
        return logits, cache

    def _prefill_ssm(self, params, h, cos, sin, cache):
        cfg = self.cfg
        sc = cfg.ssm
        B, S, _ = h.shape

        def mamba_prefill(lp, h):
            xn = L.norm_apply(lp["ln1"], h, cfg)
            mp = lp["mamba"]
            z, xs, b, c, dt_raw, di, N, nh = L._mamba_proj(mp, xn, cfg)
            w = sc.conv_width - 1
            st = {"conv_x": xs[:, -w:, :].astype(cfg.dtype),
                  "conv_b": b[:, -w:, :].astype(cfg.dtype),
                  "conv_c": c[:, -w:, :].astype(cfg.dtype)}
            xs_c = L._causal_conv(xs, mp["conv_x"])
            b_c = L._causal_conv(b, mp["conv_b"])
            c_c = L._causal_conv(c, mp["conv_c"])
            xs_c = xs_c * lax.logistic(xs_c)
            b_mat = (b_c * lax.logistic(b_c)).astype(jnp.float32)
            c_mat = (c_c * lax.logistic(c_c)).astype(jnp.float32)
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                                 + mp["dt_bias"])
            y, hf = ssd_scan_jnp(
                xs_c.reshape(B, S, nh, sc.head_dim).astype(jnp.float32), dt,
                mp["a_log"], b_mat, c_mat, mp["d_skip"],
                chunk=sc.chunk, return_state=True)
            y = y.reshape(B, S, di).astype(h.dtype)
            y = ops.rmsnorm_gated(y, z, mp["norm_g"])
            st["h"] = hf
            return h + y @ mp["w_out"], st

        if cfg.family == "ssm":
            def body(h, lp):
                return mamba_prefill(lp, h)
            h, states = lax.scan(body, h, params["layers"])
            cache["ssm"] = states
            return h, cache
        # hybrid
        k = cfg.shared_attn_every
        n_out = cfg.n_layers // k
        grouped = jax.tree.map(lambda a: a.reshape((n_out, k) + a.shape[1:]),
                               params["layers"])
        shared = params["shared"]

        def outer(h, gp):
            h, states = lax.scan(lambda hh, lp: mamba_prefill(lp, hh), h, gp)
            xn = L.norm_apply(shared["ln1"], h, cfg)
            a, kv = L.attn_prefill(shared["attn"], xn, cos, sin, cfg)
            h = h + a
            h = h + L.mlp_apply(shared["mlp"],
                                L.norm_apply(shared["ln2"], h, cfg), cfg)
            return h, (states, kv)
        h, (states, kvs) = lax.scan(outer, h, grouped)
        cache["ssm"] = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), states)
        pad = cache["k"].shape[3] - kvs[0].shape[3]
        cdt = self._cache_dtype()
        cache["k"], cache["v"] = jax.tree.map(
            lambda a: jnp.pad(a.astype(cdt),
                              ((0, 0), (0, 0), (0, 0), (0, pad),
                               (0, 0))), kvs)
        return h, cache

    # -- decode -------------------------------------------------------------------------------
    def decode_step(self, params, cache, token):
        """token (B, 1) int32; returns (logits (B,1,V), cache)."""
        cfg = self.cfg
        B = token.shape[0]
        h = params["embed"][token]
        pos = cache["pos"]
        cos1, sin1 = self._cos_sin(pos[None].astype(jnp.int32))
        # The stacked K/V cache rides in the scan's carry, and each layer
        # writes only its new row into it (attn_decode_stacked); as the
        # scan's xs/ys the whole cache would be written out again every
        # tick. With the cache donated (launch/serve.py) the row write
        # lands in the given buffers.
        if cfg.family in ("dense", "moe", "vlm"):
            def body(carry, xs):
                h, kv = carry
                lp, l = xs
                xn = L.norm_apply(lp["ln1"], h, cfg)
                a, kv = L.attn_decode_stacked(lp["attn"], xn, kv, l, pos,
                                              cfg, cos1, sin1)
                h = h + a
                if cfg.family == "moe":
                    y, _ = L.moe_apply(lp["moe"],
                                       L.norm_apply(lp["ln2"], h, cfg), cfg)
                else:
                    y = L.mlp_apply(lp["mlp"],
                                    L.norm_apply(lp["ln2"], h, cfg), cfg)
                return (h + y, kv), None
            (h, (ks, vs)), _ = lax.scan(
                body, (h, (cache["k"], cache["v"])),
                (params["layers"], jnp.arange(cfg.n_layers)))
            cache = dict(cache, k=ks, v=vs, pos=pos + 1)
        elif cfg.family == "ssm":
            def body(h, xs):
                lp, st = xs
                xn = L.norm_apply(lp["ln1"], h, cfg)
                y, st = L.mamba_decode(lp["mamba"], xn, st, cfg)
                return h + y, st
            h, states = lax.scan(body, h, (params["layers"], cache["ssm"]))
            cache = dict(cache, ssm=states, pos=pos + 1)
        else:  # hybrid
            k = cfg.shared_attn_every
            n_out = cfg.n_layers // k
            grouped = jax.tree.map(
                lambda a: a.reshape((n_out, k) + a.shape[1:]),
                params["layers"])
            gstates = jax.tree.map(
                lambda a: a.reshape((n_out, k) + a.shape[1:]), cache["ssm"])
            shared = params["shared"]

            def outer(carry, xs):
                h, kv = carry
                gp, st, g = xs

                def inner(hh, ys):
                    lp, s1 = ys
                    xn = L.norm_apply(lp["ln1"], hh, cfg)
                    y, s1 = L.mamba_decode(lp["mamba"], xn, s1, cfg)
                    return hh + y, s1
                h, st = lax.scan(inner, h, (gp, st))
                xn = L.norm_apply(shared["ln1"], h, cfg)
                a, kv = L.attn_decode_stacked(shared["attn"], xn, kv, g,
                                              pos, cfg, cos1, sin1)
                h = h + a
                h = h + L.mlp_apply(shared["mlp"],
                                    L.norm_apply(shared["ln2"], h, cfg), cfg)
                return (h, kv), st
            (h, (ks, vs)), gstates = lax.scan(
                outer, (h, (cache["k"], cache["v"])),
                (grouped, gstates, jnp.arange(n_out)))
            cache = dict(cache,
                         ssm=jax.tree.map(
                             lambda a: a.reshape((-1,) + a.shape[2:]),
                             gstates),
                         k=ks, v=vs, pos=pos + 1)
        h = L.norm_apply(params["final_norm"], h, cfg)
        logits = (h.astype(jnp.float32)
                  @ self._unembed(params).astype(jnp.float32))
        return logits, cache
