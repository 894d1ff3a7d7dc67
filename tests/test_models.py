"""Per-architecture smoke tests (reduced configs): one forward/train step
on CPU asserting output shapes + no NaNs, plus prefill↔decode consistency
against the full-sequence logits."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, applicable, cells, get_config, \
    get_smoke_config
from repro.models import get_model


def _batch(cfg, B=2, S=32, seed=1):
    kt, kl, kf = jax.random.split(jax.random.PRNGKey(seed), 3)
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(kf, (B, S, cfg.d_model),
                                            jnp.float32)
    return batch


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert np.isfinite(float(loss))
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    if cfg.family == "encdec":
        logits, cache = model.prefill(params, batch["tokens"],
                                      batch["frames"])
    else:
        logits, cache = model.prefill(params, batch["tokens"])
    assert logits.shape == (B, 1, cfg.vocab)
    tok = batch["labels"][:, :1]
    logits2, cache2 = model.decode_step(params, cache, tok)
    assert logits2.shape == (B, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2)).all()
    assert int(cache2["pos"]) == S + 1


@pytest.mark.parametrize("arch", ["minitron_4b", "qwen2_vl_2b",
                                  "mamba2_1p3b", "dbrx_132b",
                                  "zamba2_2p7b"])
def test_prefill_matches_full_forward(arch):
    """Last-position prefill logits == full forward logits at last pos."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    full = model.logits(params, tokens)
    pre, _ = model.prefill(params, tokens)
    np.testing.assert_allclose(np.asarray(pre[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("arch", ["minitron_4b", "mamba2_1p3b",
                                  "zamba2_2p7b"])
@pytest.mark.slow
def test_decode_matches_teacher_forcing(arch):
    """decode_step over a prompt reproduces full-forward logits stepwise."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 12
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    full = np.asarray(model.logits(params, tokens))
    pre_len = 4
    logits, cache = model.prefill(params, tokens[:, :pre_len])
    np.testing.assert_allclose(np.asarray(logits)[:, 0],
                               full[:, pre_len - 1], atol=3e-2, rtol=3e-2)
    for t in range(pre_len, S):
        logits, cache = model.decode_step(params, cache, tokens[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(logits)[:, 0], full[:, t],
                                   atol=3e-2, rtol=3e-2,
                                   err_msg=f"step {t}")


def _restacking_decode_step(model, params, cache, token):
    """The decode step with the stacked K/V cache as the layer scan's xs
    and ys, so every tick writes the whole cache out again: the reference
    for ``LM.decode_step``, which writes only the new row in place."""
    from jax import lax
    from repro.models import layers as L
    cfg = model.cfg
    h = params["embed"][token]
    pos = cache["pos"]
    cos1, sin1 = model._cos_sin(pos[None].astype(jnp.int32))
    if cfg.family == "dense":
        def body(h, xs):
            lp, kc, vc = xs
            xn = L.norm_apply(lp["ln1"], h, cfg)
            a, (kc, vc) = L.attn_decode(lp["attn"], xn, (kc, vc), pos,
                                        cfg, cos1, sin1)
            h = h + a
            y = L.mlp_apply(lp["mlp"], L.norm_apply(lp["ln2"], h, cfg), cfg)
            return h + y, (kc, vc)
        h, (ks, vs) = lax.scan(body, h, (params["layers"], cache["k"],
                                         cache["v"]))
        cache = dict(cache, k=ks, v=vs, pos=pos + 1)
    else:  # hybrid
        k = cfg.shared_attn_every
        n_out = cfg.n_layers // k
        group = lambda a: a.reshape((n_out, k) + a.shape[1:])
        shared = params["shared"]

        def outer(h, xs):
            gp, st, kc, vc = xs

            def inner(hh, ys):
                lp, s1 = ys
                xn = L.norm_apply(lp["ln1"], hh, cfg)
                y, s1 = L.mamba_decode(lp["mamba"], xn, s1, cfg)
                return hh + y, s1
            h, st = lax.scan(inner, h, (gp, st))
            xn = L.norm_apply(shared["ln1"], h, cfg)
            a, (kc, vc) = L.attn_decode(shared["attn"], xn, (kc, vc), pos,
                                        cfg, cos1, sin1)
            h = h + a
            h = h + L.mlp_apply(shared["mlp"],
                                L.norm_apply(shared["ln2"], h, cfg), cfg)
            return h, (st, kc, vc)
        h, (gstates, ks, vs) = lax.scan(
            outer, h, (jax.tree.map(group, params["layers"]),
                       jax.tree.map(group, cache["ssm"]), cache["k"],
                       cache["v"]))
        cache = dict(cache, k=ks, v=vs, pos=pos + 1,
                     ssm=jax.tree.map(
                         lambda a: a.reshape((-1,) + a.shape[2:]), gstates))
    h = L.norm_apply(params["final_norm"], h, cfg)
    logits = (h.astype(jnp.float32)
              @ model._unembed(params).astype(jnp.float32))
    return logits, cache


@pytest.mark.parametrize("arch", ["minitron_4b", "zamba2_2p7b"])
def test_decode_writes_cache_in_place_bitwise(arch):
    """Writing only the new K/V row into the carried cache gives the same
    logits and caches, bit for bit, as re-stacking the whole cache."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 12), 0,
                                cfg.vocab)
    _, cache = model.prefill(params, tokens[:, :6], max_seq=16)
    ref_cache = cache
    step = jax.jit(model.decode_step)
    ref_step = jax.jit(functools.partial(_restacking_decode_step, model))
    for t in range(6, 12):
        logits, cache = step(params, cache, tokens[:, t:t + 1])
        ref_logits, ref_cache = ref_step(params, ref_cache,
                                         tokens[:, t:t + 1])
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        assert jax.tree.structure(cache) == jax.tree.structure(ref_cache)
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    assert int(cache["pos"]) == 12


def test_cells_enumeration():
    cs = cells()
    assert len(cs) == 40
    runnable = [c for c in cs if c[2]]
    skipped = [c for c in cs if not c[2]]
    assert len(skipped) == 8  # long_500k × pure-attention archs
    assert all(c[1] == "long_500k" for c in skipped)
    for arch in ("mamba2_1p3b", "zamba2_2p7b"):
        assert any(c[0] == arch and c[1] == "long_500k" and c[2]
                   for c in cs)


def test_param_counts_sane():
    expect = {
        "minitron_4b": (4e9, 6e9), "mistral_nemo_12b": (11e9, 13.5e9),
        "mistral_large_123b": (115e9, 130e9), "granite_8b": (7e9, 9e9),
        "mamba2_1p3b": (1.1e9, 1.6e9), "qwen2_vl_2b": (1.3e9, 1.8e9),
        "dbrx_132b": (125e9, 140e9), "arctic_480b": (450e9, 500e9),
        "whisper_small": (0.2e9, 0.35e9), "zamba2_2p7b": (2.0e9, 3.0e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo},{hi}]"


def test_moe_active_params():
    dbrx = get_config("dbrx_132b")
    arctic = get_config("arctic_480b")
    assert dbrx.active_param_count() < 0.35 * dbrx.param_count()
    assert arctic.active_param_count() < 0.05 * arctic.param_count()


def test_vlm_mrope_positions():
    """Vision-style 3-axis positions change the logits (M-RoPE active)."""
    cfg = get_smoke_config("qwen2_vl_2b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 8
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab)
    text_pos = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
    img_pos = text_pos.at[1].set(text_pos[1] * 2).at[2].set(text_pos[2] * 3)
    h1, _ = model.forward(params, tokens, text_pos)
    h2, _ = model.forward(params, tokens, img_pos)
    assert not np.allclose(np.asarray(h1, np.float32),
                           np.asarray(h2, np.float32))


def test_hybrid_shared_block_is_tied():
    """zamba2's shared attention params are one block, reused."""
    cfg = get_smoke_config("zamba2_2p7b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "shared" in params
    # layer stack has no attention weights of its own
    assert "attn" not in params["layers"]


@pytest.mark.slow
def test_f8_kv_cache_decode():
    """fp8 KV cache (100B+ serving option): decode tracks the bf16-cache
    full-forward logits within fp8 quantization tolerance."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("mistral_large_123b"),
                              kv_cache_dtype="f8")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 10), 0,
                                cfg.vocab)
    full = np.asarray(model.logits(params, tokens))
    logits, cache = model.prefill(params, tokens[:, :4])
    assert str(cache["k"].dtype) == "float8_e4m3fn"
    errs = []
    for t in range(4, 10):
        logits, cache = model.decode_step(params, cache, tokens[:, t:t + 1])
        errs.append(np.abs(np.asarray(logits)[:, 0] - full[:, t]).max())
    assert max(errs) < 0.35 * np.abs(full).max()
