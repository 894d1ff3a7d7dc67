"""Operation and byte counts against numbers worked out by hand."""
import pytest

from chipbench import bench
from chipbench import flops as F

MINITRON = bench.config("minitron-4b")["model"]


def test_minitron_layer_params():
    attn = 3072 * (3072 + 2 * 8 * 128) + 3072 * 3072   # wq, wk, wv; wo
    mlp = 3 * 3072 * 9216                              # wg, wu, wd
    assert attn == 25_165_824 and mlp == 84_934_656
    assert F.layer_params(MINITRON) == 110_100_480


def test_decode_step_flops_and_bytes():
    # 16 tokens at position 512: 32 layers of matrices plus the 3072 x
    # 256000 unembedding, and 513 keys per head in each of 32 layers
    mats = 2 * 16 * (32 * 110_100_480 + 3072 * 256_000)
    attn = 4 * 16 * 32 * 24 * 128 * 513
    assert mats == 137_908_715_520 and attn == 3_227_516_928
    assert F.lm_decode_flops(MINITRON, 16, 512) == mats + attn
    weights = (32 * 110_100_480 + 3072 * 256_000) * 2
    kv_read = 2 * 32 * 16 * 8 * 128 * 513 * 2
    kv_write = 2 * 32 * 16 * 8 * 128 * 2
    assert weights == 8_619_294_720
    assert F.lm_decode_bytes(MINITRON, 16, 512) == (
        weights + kv_read + kv_write)
    assert F.lm_decode_bytes(MINITRON, 16, 512) == 9_697_230_848


def test_prefill_flops():
    mats = 2 * 4 * 2048 * 32 * 110_100_480
    attn = 4 * 4 * 32 * 24 * 128 * (2048 * 2049 // 2)  # causal pairs
    unembed = 2 * 4 * 3072 * 256_000                    # last position
    assert F.lm_prefill_flops(MINITRON, 4, 2048) == mats + attn + unembed


def test_encdec_forward_flops_tiny():
    c = {"d_model": 4, "d_ff": 8, "n_heads": 2, "head_dim": 2,
         "n_layers": 1, "n_enc_layers": 1, "vocab": 10}
    # encoder: projections 2*3*(4*4*4) + MLP 2*3*(2*4*8), scores 4*2*2*9
    enc = 2 * 3 * 64 + 2 * 3 * 64 + 4 * 2 * 2 * 9
    # decoder: self q/k/v/o + cross q/o + MLP on 3 tokens, cross k/v on
    # 3 frames, causal self scores (6 pairs), cross scores (9 pairs)
    dec = (2 * 3 * (64 + 32 + 64) + 2 * 3 * 32 + 4 * 2 * 2 * 6
           + 4 * 2 * 2 * 9)
    unembed = 2 * 3 * 4 * 10
    assert (enc, dec, unembed) == (912, 1392, 240)
    assert F.encdec_forward_flops(c, 1, 3, 3) == 2544
    assert F.encdec_train_flops(c, 1, 3, 3) == 3 * 2544


@pytest.mark.parametrize("kernel,per_element", [
    ("tile_rmsnorm", 4), ("tile_swiglu", 5), ("tile_rotary", 3),
    ("tile_layernorm", 7), ("tile_gelu", 8), ("tile_adamw", 12)])
def test_tile_kernel_cost(kernel, per_element):
    x = ("f32", (8192, 3072), 0)
    operands = [x, ("f32", (1, 3072), 0), ("f32", (1,), 0)]
    c = bench.kernel_cost(kernel).cost(operands, [x])
    assert c["flops"] == per_element * 8192 * 3072
    assert c["hbm_bytes"] == 8192 * 3072 * 4 * 2 + 3072 * 4 + 4
    assert c["vmem_read_bytes"] == c["vmem_write_bytes"] == 0
    # an operand the compiler keeps in VMEM moves no HBM bytes
    c = bench.kernel_cost(kernel).cost([("f32", (8192, 3072), 1)],
                                      [("bf16", (8192, 3072), 1)])
    assert c["hbm_bytes"] == 0
    assert c["vmem_read_bytes"] == 8192 * 3072 * 4
    assert c["vmem_write_bytes"] == 8192 * 3072 * 2


def test_flash_cost():
    q = ("bf16", (384, 512, 128), 0)
    kv = ("bf16", (16, 8, 512, 128), 0)
    o, lse = ("bf16", (384, 512, 128), 0), ("f32", (384, 512, 128), 0)
    c = bench.kernel_cost("flash_attention").cost([q, kv, kv], [o, lse])
    assert c["flops"] == 4 * 384 * 128 * (512 * 513 / 2)
    assert c["hbm_bytes"] == (384 * 512 * 128 * 2 * 2
                              + 16 * 8 * 512 * 128 * 2 * 2
                              + 384 * 512 * 128 * 4)


def test_roofline_share_never_passes_time_spent():
    from chipbench import trace as T
    text = ("%tile_rmsnorm.1 = f32[8192,3072]{1,0} custom-call("
            "f32[8192,3072]{1,0} %x, f32[1,3072]{1,0} %g, f32[1]{0} %s)")
    nbytes = 8192 * 3072 * 4 * 2 + 3072 * 4 + 4
    fastest_ns = nbytes / 819e9 * 1e9
    tr = T.Trace({"/device:TPU:0": [T.Op(text, 0, 2 * fastest_ns)]},
                 [], (0, 3 * fastest_ns))
    view = bench.View(tr, MINITRON, bench.peaks("TPU v5 lite"), None)
    share = bench.metric_reader("tile_roofline.prefill").read(view)
    assert share == pytest.approx(50.0)
