"""Operations and bytes the model's work needs, counted from its shapes.

These are the counts the benchmark's utilisation metrics divide by time.
They count what the algorithm requires: causal attention over the keys
it may see (not the reserved cache), the unembedding only where logits
are used, no recomputation. ``cfg`` is the ``model`` block of a
configuration file.
"""
from __future__ import annotations

from typing import Dict


def _attn_params(c: Dict) -> int:
    d = c["d_model"]
    qd = c["n_heads"] * c["head_dim"]
    kvd = c["n_kv_heads"] * c["head_dim"]
    return d * (qd + 2 * kvd) + qd * d


def _mlp_params(c: Dict) -> int:
    n_mats = 3 if c["act"] == "swiglu" else 2
    return n_mats * c["d_model"] * c["d_ff"]


def layer_params(c: Dict) -> int:
    """Matrix parameters of one decoder layer of the dense LM."""
    return _attn_params(c) + _mlp_params(c)


def _causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def lm_prefill_flops(c: Dict, batch: int, seq: int) -> float:
    """One prefill of ``batch`` prompts of ``seq`` tokens; logits of the
    last position only."""
    L, H, hd = c["n_layers"], c["n_heads"], c["head_dim"]
    mats = 2.0 * batch * seq * L * layer_params(c)
    attn = 4.0 * batch * L * H * hd * _causal_pairs(seq)
    unembed = 2.0 * batch * c["d_model"] * c["vocab"]
    return mats + attn + unembed


def lm_decode_flops(c: Dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` tokens at position ``pos`` (each
    attends to ``pos + 1`` keys)."""
    L, H, hd = c["n_layers"], c["n_heads"], c["head_dim"]
    mats = 2.0 * batch * (L * layer_params(c) + c["d_model"] * c["vocab"])
    attn = 4.0 * batch * L * H * hd * (pos + 1)
    return mats + attn


def lm_decode_bytes(c: Dict, batch: int, pos: int) -> float:
    """Bytes one decode step must move: every layer's weights and the
    unembedding once, the ``pos + 1`` cached keys and values each
    sequence attends to, the new key and value written back."""
    w = 2 if c["dtype"] == "bfloat16" else 4
    L, KH, hd = c["n_layers"], c["n_kv_heads"], c["head_dim"]
    weights = (L * layer_params(c) + c["d_model"] * c["vocab"]) * w
    kv_read = 2.0 * L * batch * KH * hd * (pos + 1) * w
    kv_write = 2.0 * L * batch * KH * hd * w
    return weights + kv_read + kv_write


def encdec_forward_flops(c: Dict, batch: int, seq: int,
                         enc_seq: int) -> float:
    """Forward pass of the encoder-decoder for a training batch: encoder
    over ``enc_seq`` frames, decoder over ``seq`` tokens with causal
    self-attention and cross-attention, logits at every position."""
    d, dff, H, hd = c["d_model"], c["d_ff"], c["n_heads"], c["head_dim"]
    qd = H * hd
    mlp = 2 * d * dff
    enc = c["n_enc_layers"] * (
        2.0 * batch * enc_seq * (4 * d * qd + mlp)
        + 4.0 * batch * H * hd * enc_seq * enc_seq)
    dec = c["n_layers"] * (
        2.0 * batch * seq * (4 * d * qd + 2 * d * qd + mlp)  # self, cross q/o
        + 2.0 * batch * enc_seq * 2 * d * qd                 # cross k/v
        + 4.0 * batch * H * hd * _causal_pairs(seq)
        + 4.0 * batch * H * hd * seq * enc_seq)
    unembed = 2.0 * batch * seq * d * c["vocab"]
    return enc + dec + unembed


def encdec_train_flops(c: Dict, batch: int, seq: int, enc_seq: int) -> float:
    """Forward and backward: three times the forward's operations."""
    return 3.0 * encdec_forward_flops(c, batch, seq, enc_seq)
