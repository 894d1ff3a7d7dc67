"""Per-kernel validation: Pallas (interpret=True) + saturated-jnp vs the
pure-jnp oracles in repro.kernels.ref, swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.core.telemetry import telemetry
from repro.kernels.flash_attention import attention_tiles, flash_attention
from repro.kernels.ssd_scan import ssd_scan, ssd_scan_jnp, ssd_decode_step
from repro.kernels.tile_programs import PROGRAMS, get_tile_op

SHAPES = [(4, 128), (3, 256), (16, 384)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_rmsnorm_sweep(shape, dtype, impl, rng):
    x = jnp.asarray(rng.normal(size=shape), dtype)
    g = jnp.asarray(rng.normal(size=shape[-1:]), dtype)
    op = get_tile_op("rmsnorm")
    fn = op.apply if impl == "pallas" else op.jax_ref
    out = fn(x, g, eps=1e-6)
    want = ref.rmsnorm_ref(x.astype(jnp.float32), g.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("name,n_in", [
    ("swiglu", 2), ("softmax", 1), ("gelu", 1)])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_elementwise_sweep(name, n_in, impl, rng):
    for shape in SHAPES:
        xs = [jnp.asarray(rng.normal(size=shape), jnp.float32)
              for _ in range(n_in)]
        op = get_tile_op(name)
        fn = op.apply if impl == "pallas" else op.jax_ref
        out = fn(*xs)
        want = {"swiglu": lambda: ref.swiglu_ref(*xs),
                "softmax": lambda: ref.softmax_ref(*xs),
                "gelu": lambda: ref.gelu_ref(*xs)}[name]()
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_layernorm(impl, rng):
    x = jnp.asarray(rng.normal(size=(6, 256)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    op = get_tile_op("layernorm")
    fn = op.apply if impl == "pallas" else op.jax_ref
    np.testing.assert_allclose(np.asarray(fn(x, g, b, eps=1e-6)),
                               np.asarray(ref.layernorm_ref(x, g, b)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_rmsnorm_gated(impl, rng):
    x = jnp.asarray(rng.normal(size=(6, 128)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(6, 128)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    op = get_tile_op("rmsnorm_gated")
    fn = op.apply if impl == "pallas" else op.jax_ref
    np.testing.assert_allclose(np.asarray(fn(x, z, g, eps=1e-6)),
                               np.asarray(ref.rmsnorm_gated_ref(x, z, g)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_rotary(impl, rng):
    q = jnp.asarray(rng.normal(size=(2, 3, 4, 128)), jnp.float32)
    cos = jnp.asarray(rng.normal(size=(1, 3, 1, 128)), jnp.float32)
    sin = jnp.asarray(rng.normal(size=(1, 3, 1, 128)), jnp.float32)
    ops.set_impl(impl)
    try:
        out = ops.rotary(q, cos, sin)
    finally:
        ops.set_impl(None)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.rotary_ref(q, cos, sin)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_adamw_kernel(impl, rng):
    p = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(8, 256)) * 0.1, jnp.float32)
    v = jnp.asarray(abs(rng.normal(size=(8, 256))) * 0.01, jnp.float32)
    kw = dict(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
              inv_bc1=1.3, inv_bc2=1.1)
    op = get_tile_op("adamw")
    fn = op.apply if impl == "pallas" else op.jax_ref
    out = fn(p, g, m, v, **kw)
    want = ref.adamw_ref(p, g, m, v, **kw)
    for a, b in zip(out, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-6, rtol=2e-6)


def test_adamw_bulk_load_and_fma():
    st = get_tile_op("adamw").pk.stats
    assert st.loads_before_compute == st.n_loads == 4
    assert st.n_fma >= 2


# -- flash attention ------------------------------------------------------------
@pytest.mark.parametrize("B,H,KH,S,D", [
    (2, 4, 2, 128, 64), (1, 2, 2, 256, 128), (2, 8, 1, 128, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, KH, S, D, causal, rng):
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    want = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("S,tile", [(64, 64), (128, 128), (384, 128),
                                    (512, 512), (768, 256), (2048, 512),
                                    (4096, 512)])
def test_attention_tiles(S, tile):
    assert attention_tiles(S) == tile
    assert S % tile == 0


def test_attention_tiles_refuses_a_ragged_sequence():
    with pytest.raises(ValueError, match="multiple of 128"):
        attention_tiles(200)


FLASH_512 = {"causal": (4, 4, True), "noncausal": (4, 4, False),
             "gqa": (4, 2, True)}


def _qkv(rng, H, KH, S=512, D=128):
    return tuple(jnp.asarray(rng.normal(size=(1, h, S, D)), jnp.float32)
                 for h in (H, KH, KH))


@pytest.mark.parametrize("case", sorted(FLASH_512))
def test_flash_attention_chosen_tiles_s512(case, rng):
    """No tiles given: one 512x512 tile over the whole sequence."""
    H, KH, causal = FLASH_512[case]
    q, k, v = _qkv(rng, H, KH)
    out = flash_attention(q, k, v, causal=causal)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("case", sorted(FLASH_512))
def test_flash_attention_grad_chosen_tiles_s512(case, rng):
    """The backward runs with the forward's (chosen) tiles."""
    H, KH, causal = FLASH_512[case]
    q, k, v = _qkv(rng, H, KH)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) * w)

    got = jax.grad(loss(flash_attention), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.attention_ref), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("S,key", [(512, "512x512"), (128, "128x128")])
def test_flash_tiles_recorded_in_telemetry(S, key):
    """A traced launch records its tiles; the shape (an odd head count
    no other test uses) keeps jit's trace cache from hiding the call."""
    q = jax.ShapeDtypeStruct((1, 3, S, 8), jnp.float32)
    before = telemetry().snapshot()["flash_tiles"].get(key, 0)
    ops.set_impl("pallas")
    try:
        jax.eval_shape(lambda q: ops.attention(q, q, q), q)
    finally:
        ops.set_impl(None)
    assert telemetry().snapshot()["flash_tiles"].get(key, 0) == before + 1
    last = [e for e in telemetry().events if e["kind"] == "flash_tiles"][-1]
    assert last == {"kind": "flash_tiles", "tiles": key,
                    "q_shape": (1, 3, S, 8)}


def test_decode_attention_matches_full(rng):
    B, H, KH, S, D = 2, 4, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KH, S, D)), jnp.float32)
    full = ref.attention_ref(q, k, v, causal=True)
    got = ops.attention_decode(q[:, :, -1:], k, v)
    np.testing.assert_allclose(np.asarray(got)[:, :, 0],
                               np.asarray(full)[:, :, -1],
                               atol=2e-5, rtol=2e-5)


# -- SSD -------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 64, 2, 16, 16, 16), (1, 128, 4, 32, 64, 32), (2, 96, 3, 16, 8, 32)])
def test_ssd_sweep(B, S, H, P, N, chunk, rng):
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(-1, 1, size=(H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
    d = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    want = ref.ssd_ref(x, dt, a_log, bm, cm, d)
    got_pl = ssd_scan(x, dt, a_log, bm, cm, d, chunk=chunk)
    got_jnp = ssd_scan_jnp(x, dt, a_log, bm, cm, d, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_ssd_decode_consistency(rng):
    B, S, H, P, N = 1, 32, 2, 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(-1, 1, size=(H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
    d = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    want = ref.ssd_ref(x, dt, a_log, bm, cm, d)
    h = jnp.zeros((B, H, N, P), jnp.float32)
    outs = []
    for t in range(S):
        h, y = ssd_decode_step(h, x[:, t], dt[:, t], a_log, bm[:, t],
                               cm[:, t], d)
        outs.append(y)
    got = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_ssd_state_handoff(rng):
    """Prefill state == decode-from-scratch state (cache correctness)."""
    B, S, H, P, N = 1, 64, 2, 16, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(-1, 1, size=(H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
    d = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    _, h_pref = ssd_scan_jnp(x, dt, a_log, bm, cm, d, chunk=16,
                             return_state=True)
    h = jnp.zeros((B, H, N, P), jnp.float32)
    for t in range(S):
        h, _ = ssd_decode_step(h, x[:, t], dt[:, t], a_log, bm[:, t],
                               cm[:, t], d)
    np.testing.assert_allclose(np.asarray(h_pref), np.asarray(h),
                               atol=2e-4, rtol=2e-4)


# -- property: tile ops are deterministic and shape-preserving ---------------------
@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 9), d=st.sampled_from([128, 256]),
       seed=st.integers(0, 100))
def test_tile_op_shape_property(rows, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    op = get_tile_op("rmsnorm")
    out = op.apply(x, g, eps=1e-6)
    assert out.shape == x.shape
    out2 = op.apply(x, g, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
