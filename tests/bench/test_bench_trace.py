"""The reduction from a profiler trace to per-layer metrics."""
import json
import pathlib

import pytest

from chipbench import bench
from chipbench import trace as T

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def _op(text, s, e):
    return T.Op(text, s, e)


SWIGLU = ("%tile_swiglu.3 = bf16[8192,9216]{1,0:T(8,128)(2,1)} custom-call("
          "bf16[8192,9216]{1,0:T(8,128)(2,1)} %fusion.69, bf16[8192,9216]"
          "{1,0:T(8,128)(2,1)} %fusion.70), custom_call_target=\"tpu_custom_call\"")
WHILE = ("%while.2 = (s32[]{:T(128)}, bf16[16,1,3072]{2,0,1:T(8,128)(2,1)}) "
         "while((s32[]{:T(128)}, bf16[16,1,3072]{2,0,1}) %tuple), body=%body")
FLASH = ("%flash_attention.3 = (bf16[384,512,128]{2,1,0:T(8,128)(2,1)}, "
         "f32[384,512,128]{2,1,0:T(8,128)}) custom-call(bf16[384,512,128]"
         "{2,1,0:T(8,128)(2,1)} %bitcast.1, bf16[16,8,512,128]{3,2,1,0} %a, "
         "bf16[16,8,512,128]{3,2,1,0} %b), custom_call_target=\"tpu_custom_call\"")


def small_trace():
    ops = [_op(WHILE, 100, 400),            # container around the two below
           _op(SWIGLU, 120, 200),
           _op("%fusion.7 = bf16[16,9216]{1,0} fusion(bf16[16,3072]{1,0} %p)",
               250, 390),
           _op(FLASH, 500, 700),
           _op(SWIGLU, 950, 1100)]          # ends after the window
    spans = [T.Span("chipbench.traced", 50, 1000, {}),
             T.Span("chipbench.submit", 60, 990, {}),
             T.Span("chipbench.decode", 400, 480, {"batch": 16, "pos": 512}),
             T.Span("chipbench.prefill", 700, 900, {"batch": 4, "seq": 2048})]
    return T.Trace({"/device:TPU:0": ops}, spans, (50, 1000))


def test_hlo_text_parts():
    op = _op(SWIGLU, 0, 1)
    assert op.kernel == "tile_swiglu"
    assert op.opcode == "custom-call"
    operands, results = op.shapes()
    assert operands == [("bf16", (8192, 9216), 0)] * 2
    assert results == [("bf16", (8192, 9216), 0)]
    assert T.nbytes(operands + results) == 3 * 8192 * 9216 * 2
    assert _op(WHILE, 0, 1).opcode == "while"
    clone = _op("%broadcast.80.clone = bf16[12,16]{1,0} broadcast(bf16[] %c)",
                0, 1)
    assert (clone.kernel, clone.opcode) == ("broadcast", "broadcast")
    operands, results = _op(FLASH, 0, 1).shapes()
    assert results == [("bf16", (384, 512, 128), 0),
                       ("f32", (384, 512, 128), 0)]
    assert [d for _, d, _ in operands] == [(384, 512, 128), (16, 8, 512, 128),
                                           (16, 8, 512, 128)]


def test_memory_space_from_layout():
    text = ("%tile_rmsnorm.12 = f32[16,3072]{1,0:T(8,128)S(1)} custom-call("
            "f32[16,3072]{1,0:T(8,128)S(1)} %a, f32[1,3072]{1,0:T(1,128)} %g,"
            " f32[1]{0:T(128)} %s), custom_call_target=\"tpu_custom_call\"")
    operands, results = _op(text, 0, 1).shapes()
    assert [s for _, _, s in operands] == [1, 0, 0]
    assert results == [("f32", (16, 3072), 1)]
    assert T.nbytes(operands, 1) == 16 * 3072 * 4
    assert T.nbytes(operands, 0) == 3072 * 4 + 4


def test_busy_and_idle_gaps():
    tr = small_trace()
    # busy: [100, 400] and [500, 700] and [950, 1000] (clipped)
    assert T.busy_s(tr) == pytest.approx((300 + 200 + 50) * 1e-9)
    assert T.idle_gaps(tr, "/device:TPU:0") == [(50, 100), (400, 500),
                                                 (700, 950)]
    assert tr.window_s == pytest.approx(950e-9)


def test_gap_attribution_names_innermost_host_span():
    tr = small_trace()
    assert T.host_activity(tr, 450) == "chipbench.decode"
    assert T.host_activity(tr, 800) == "chipbench.prefill"
    assert T.host_activity(tr, 950) == "chipbench.submit"
    assert T.host_activity(tr, 995) == "outside harness spans"
    bd = T.breakdown(tr)
    gaps = dict((k, v) for k, v in bd["idle_gaps"])
    assert gaps["chipbench.prefill"] == pytest.approx(250e-9)
    assert gaps["chipbench.decode"] == pytest.approx(100e-9)


def test_kernel_time_leaves_out_containers_and_edges():
    tr = small_trace()
    names = [o.kernel for o in T.kernel_ops(tr)]
    assert names == ["tile_swiglu", "fusion", "flash_attention"]
    bd = dict((k, v) for k, v in T.breakdown(tr)["device_ops"])
    assert bd["flash_attention"] == pytest.approx(200e-9)
    assert "while" not in bd


def test_per_layer_readers_on_small_trace():
    tr = small_trace()
    cfg = bench.config("minitron-4b")["model"]
    peaks = bench.peaks("TPU v5 lite")
    view = bench.View(tr, cfg, peaks, 1.5)
    idle = bench.metric_reader("idle_share.decode").read(view)
    assert idle == pytest.approx(100 * (1 - 550 / 950))
    share = bench.metric_reader("tile_share.decode").read(view)
    assert share == pytest.approx(100 * 80 / 550)
    assert bench.metric_reader("sat_build_s").read(view) == 1.5
    # one decode step and one prefill span lie in the window
    from chipbench import flops as F
    want = (F.lm_decode_flops(cfg, 16, 512)
            + F.lm_prefill_flops(cfg, 4, 2048))
    mfu = bench.metric_reader("mfu.decode").read(view)
    assert mfu == pytest.approx(100 * want / 950e-9 / 197e12)


def test_readers_return_nothing_without_their_events():
    tr = T.Trace({"/device:TPU:0": []}, [T.Span("chipbench.traced", 0, 10,
                                                {})], (0, 10))
    view = bench.View(tr, bench.config("minitron-4b")["model"],
                      bench.peaks("TPU v5 lite"), None)
    for name in ("tile_roofline.decode", "flash_roofline.prefill",
                 "mfu.decode", "mfu_hbm.decode", "tile_share.decode"):
        assert bench.metric_reader(name).read(view) is None


def test_json_round_trip():
    tr = small_trace()
    back = T.from_json(T.to_json(tr))
    assert back.window == tr.window
    assert [o.text for o in back.ops["/device:TPU:0"]] == \
        [o.text for o in tr.ops["/device:TPU:0"]]
    assert back.spans[2].stats == {"batch": 16, "pos": 512}


# Slices of traces recorded on a TPU v5 lite by the harness: 30 ms of a
# whisper-small training step, 8 ms of a minitron-4b decode step.
RECORDED = {
    "whisper-small.train": {"busy_s": 0.02401436, "idle": 19.952133,
                            "tile_roofline": 71.360207,
                            "flash_roofline": 4.786453,
                            "kernels": {"flash_attention", "tile_gelu",
                                        "tile_layernorm"}},
    "minitron-4b.decode": {"busy_s": 0.007731222, "idle": 3.359725,
                           "tile_roofline": 6.883261, "flash_roofline": None,
                           "kernels": {"tile_rmsnorm", "tile_rotary",
                                       "tile_swiglu"}},
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_trace_reduction(cell):
    want = RECORDED[cell]
    tr = T.from_json((FIXTURES / f"trace_{cell}.json").read_text())
    view = bench.View(tr, bench.config(cell.split(".")[0])["model"],
                      bench.peaks("TPU v5 lite"), None)
    assert T.busy_s(tr) == pytest.approx(want["busy_s"], rel=1e-6)
    assert T.busy_s(tr) <= tr.window_s
    read = lambda m: bench.metric_reader(m).read(view)  # noqa: E731
    assert read("idle_share.x") == pytest.approx(want["idle"], rel=1e-5)
    assert read("tile_roofline.x") == pytest.approx(want["tile_roofline"],
                                                    rel=1e-5)
    if want["flash_roofline"] is None:
        assert read("flash_roofline.x") is None
    else:
        assert read("flash_roofline.x") == pytest.approx(
            want["flash_roofline"], rel=1e-5)
    kernels = {o.kernel for o in T.kernel_ops(tr)}
    assert want["kernels"] <= kernels
    # no kernel reads above its roofline: VMEM-resident operands move no
    # HBM bytes (counting them at HBM bandwidth read up to 565 % here)
    roof = bench.metric_reader("roofline")
    for k in want["kernels"]:
        assert roof.share(view, lambda name: name == k) <= 100.0
    # every idle gap is put down to a harness span or to none
    for name, seconds in T.breakdown(tr)["idle_gaps"]:
        assert name.startswith("chipbench.") or name == \
            "outside harness spans"
        assert seconds > 0
