"""The program's own spans and layer scopes, and the readers of the
serving loop and model step layers that use them."""
import json
import pathlib

import jax
import numpy as np
import pytest

from chipbench import bench
from chipbench import program_trace as P
from chipbench import trace as T

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def _view(trace, cell="minitron-4b.decode"):
    return bench.View(trace, bench.config(cell.split(".")[0])["model"],
                      bench.peaks("TPU v5 lite"), 0.25)


def _read(metric, view):
    return bench.metric_reader(metric).read(view)


# -- the serving loop's spans, recorded on the CPU ---------------------------------
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from repro.launch.serve import Request, Server
    srv = Server("minitron-4b", smoke=True, max_batch=2, seed=3)
    calls = {"decode": 0, "prefill": 0}
    decode, prefill = srv._decode, srv._prefill_batch

    # wrapped as the harness's serve driver wraps them
    def counted_decode(*a):
        calls["decode"] += 1
        with jax.profiler.TraceAnnotation("chipbench.decode"):
            return decode(*a)

    def counted_prefill(*a):
        calls["prefill"] += 1
        with jax.profiler.TraceAnnotation("chipbench.prefill"):
            return prefill(*a)

    srv._decode, srv._prefill_batch = counted_decode, counted_prefill
    rng = np.random.default_rng(0)
    # two batches: max_new 4 and 2 (4 steps), then 3 alone (3 steps)
    reqs = [Request(rid=i, prompt=rng.integers(1, srv.cfg.vocab, size=8)
                    .astype(np.int32), max_new=n)
            for i, n in enumerate((4, 2, 3))]
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("chipbench.traced"):
        out = srv.generate(reqs)
    jax.profiler.stop_trace()
    return srv, calls, out, trace_dir


def test_serve_spans_present_and_nested(served):
    _, _, _, trace_dir = served
    tr = P.load_xplane(trace_dir)
    by = {}
    for s in tr.spans:
        by.setdefault(s.name, []).append(s)
    assert [s.stats for s in by["repro.serve.prefill"]] == [
        {"batch": 2, "seq": 8}, {"batch": 1, "seq": 8}]
    # prefill, then a readback before each tick and one after the last
    loop = sorted(by["repro.serve.prefill"] + by["repro.serve.readback"],
                  key=lambda s: s.start)
    assert [s.name.split(".")[-1] for s in loop] == \
        ["prefill"] + ["readback"] * 4 + ["prefill"] + ["readback"] * 3
    assert all(a.end <= b.start for a, b in zip(loop, loop[1:]))
    lo, hi = tr.window
    assert all(lo <= s.start < s.end <= hi for s in loop)
    # the harness's spans nest inside the program's: its prefill inside
    # the program's, its decode between two readbacks
    for inner, outer in zip(by["chipbench.prefill"],
                            by["repro.serve.prefill"]):
        assert outer.start <= inner.start < inner.end <= outer.end
    for d in by["chipbench.decode"]:
        assert not any(s.start < d.end and d.start < s.end for s in loop)
    # the harness's reduction of the same profile still reads it
    assert T.load_xplane(trace_dir).window == tr.window


def test_readbacks_are_ticks_plus_one_per_batch(served):
    srv, _, _, trace_dir = served
    tr = P.load_xplane(trace_dir)
    readbacks = [s for s in tr.spans if s.name == "repro.serve.readback"]
    ticks = srv.metrics["decode_ticks"]
    assert ticks == 3 + 2
    assert len(readbacks) == ticks + 2
    assert [s.stats["syncs"] for s in readbacks] == [2, 2, 1, 1, 1, 1, 1]


def test_syncs_sum_to_the_host_sync_counter(served):
    srv, _, out, trace_dir = served
    tr = P.load_xplane(trace_dir)
    syncs = sum(s.stats["syncs"] for s in tr.spans
                if s.name == "repro.serve.readback")
    assert syncs == srv.metrics["host_syncs"] == 4 + 2 + 3
    assert sum(len(v) for v in out.values()) == syncs


def test_decode_and_prefill_called_once_per_tick_and_batch(served):
    srv, calls, _, _ = served
    assert calls == {"decode": srv.metrics["decode_ticks"], "prefill": 2}
    assert srv.metrics["prefills"] == 2


# -- the readers, on a synthetic trace whose values are worked by hand -----------
def synthetic():
    # the harness's trace format, with the op scopes beside it
    text = (FIXTURES / "program_trace_synthetic.json").read_text()
    return P.scoped(T.from_json(text), json.loads(text)["scopes"])


# window [0, 1000] ns; busy [100, 400] + [600, 700] + [900, 950] +
# [990, 1000] = 460; idle [0, 100] + [400, 600] + [700, 900] + [950, 990]
HAND = {
    # readbacks [400, 500] and [700, 800] u [780, 920]: idle 100 + 200
    "readback_idle_share.decode": 30.0,
    "readback_idle_share.prefill": 30.0,
    # the readbacks that start in the window read 16, 15 and 16
    "syncs_per_tick.decode": 47 / 3,
    # prefill [0, 120]: idle 100
    "prefill_host_share.prefill": 10.0,
    # attn ops 100 + 50 (a transposed scope) + 100 of 460 busy
    "attn_share.decode": 100 * 250 / 460,
    "attn_share.prefill": 100 * 250 / 460,
    "attn_share.train": 100 * 250 / 460,
}


@pytest.mark.parametrize("metric", sorted(HAND))
def test_program_readers_on_synthetic_trace(metric):
    assert _read(metric, _view(synthetic())) == pytest.approx(
        HAND[metric], rel=1e-12)


def test_scope_components():
    op = P.ScopedOp("x", 0, 1, "jit(loss)/transpose(jvp(attn))/mul")
    assert op.in_scope("attn")
    assert not op.in_scope("mlp")
    assert not P.ScopedOp("x", 0, 1, "jit(f)/attn_out/mul").in_scope("attn")
    assert not P.ScopedOp("x", 0, 1).in_scope("attn")
    tr = synthetic()
    assert P.scope_share(tr, "mlp") == pytest.approx(100 * 100 / 460)
    assert P.scope_share(tr, "unembed") == pytest.approx(100 * 50 / 460)


def test_op_scopes_read_from_event_metadata(tmp_path):
    # an XSpace as a TPU profile lays it out: the op_name is the tf_op
    # stat of each event's metadata, as a string or an interned reference
    space = P._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "flops"),
                      (3, "jit(f)/transpose(jvp(mlp))/mul:")):
        plane.stat_metadata.add(key=key).value.name = name
    meta = {10: [(1, "jit(f)/attn/dot_general:", 0), (2, "", 0)],
            11: [(1, "", 3)], 12: [(2, "", 0)]}
    for key, stats in meta.items():
        entry = plane.event_metadata.add(key=key)
        for stat_id, text, ref in stats:
            entry.value.stats.add(metadata_id=stat_id, str_value=text,
                                  ref_value=ref)
    plane.lines.add(name="XLA Modules").events.add(metadata_id=12)
    ops_line = plane.lines.add(name="XLA Ops")
    for key in (10, 11, 12, 10):
        ops_line.events.add(metadata_id=key)
    space.planes.add(name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert P.op_scopes(str(path)) == {"/device:TPU:0": [
        "jit(f)/attn/dot_general:", "jit(f)/transpose(jvp(mlp))/mul:", "",
        "jit(f)/attn/dot_general:"]}


def test_program_trace_json_round_trip():
    # the harness's JSON keeps a program trace's ops and spans; the scopes
    # travel beside it
    tr = synthetic()
    back = P.scoped(T.from_json(T.to_json(tr)),
                    {d: [o.scope for o in ops] for d, ops in tr.ops.items()})
    assert back.window == tr.window
    fields = lambda t: [(o.text, o.start, o.end, o.scope)  # noqa: E731
                        for o in t.ops["/device:TPU:0"]]
    assert fields(back) == fields(tr)
    assert [(s.name, s.stats) for s in back.spans] == \
        [(s.name, s.stats) for s in tr.spans]


def test_scopes_that_miss_ops_are_an_error():
    tr = synthetic()
    short = {"/device:TPU:0": ["jit(f)/attn/mul"]}
    with pytest.raises(ValueError, match="/device:TPU:0: 8 events"):
        P.scoped(tr, short)


def test_unreadable_profile_names_its_path(tmp_path):
    path = tmp_path / "bad.xplane.pb"
    path.write_bytes(b"not a profile")
    with pytest.raises(RuntimeError, match="bad.xplane.pb"):
        P._with_program(synthetic(), str(path))


def test_program_readers_silent_on_a_program_without_spans_or_scopes():
    # what the profile of a program without repro.* spans or named
    # scopes gives: harness spans and ops with unscoped op names
    tr = synthetic()
    bare = P.ProgramTrace(
        {d: [P.ScopedOp(o.text, o.start, o.end, "jit(decode_step)/mul")
             for o in ops] for d, ops in tr.ops.items()},
        [s for s in tr.spans if s.name.startswith("chipbench.")], tr.window)
    for metric in HAND:
        assert _read(metric, _view(bare)) is None
    # a harness trace with no profile of its window on disk
    plain = T.Trace({"/device:TPU:0": []},
                    [T.Span("chipbench.traced", -7, -3, {})], (-7, -3))
    for metric in HAND:
        assert _read(metric, _view(plain)) is None


def test_existing_readers_ignore_program_spans():
    # the harness's readers read chipbench.* spans by name: a trace with
    # the program's spans beside them reads as the same trace without
    tr = synthetic()
    harness_only = P.ProgramTrace(
        tr.ops, [s for s in tr.spans if not s.name.startswith("repro.")],
        tr.window)
    for metric in ("idle_share.decode", "mfu.decode", "mfu_hbm.decode",
                   "tile_share.decode", "tile_roofline.decode"):
        assert _read(metric, _view(tr)) == _read(metric, _view(harness_only))


# -- the harness's readers, pinned on the recorded traces ---------------------------
PINNED = [
    ("minitron-4b.decode", "idle_share", 3.3597250000000023),
    ("minitron-4b.decode", "mfu", 8.955344698477157),
    ("minitron-4b.decode", "mfu_hbm", 148.00413382173383),
    ("minitron-4b.decode", "tile_roofline", 6.8832613783978545),
    ("minitron-4b.decode", "tile_share", 0.4513258059333958),
    ("minitron-4b.decode", "flash_roofline", None),
    ("minitron-4b.decode", "sat_build_s", 0.25),
    ("whisper-small.train", "idle_share", 19.952133333333332),
    ("whisper-small.train", "mfu", 217.5789877279188),
    ("whisper-small.train", "mfu_hbm", None),
    ("whisper-small.train", "tile_roofline", 71.36020727337693),
    ("whisper-small.train", "tile_share", 6.1441945569234395),
    ("whisper-small.train", "flash_roofline", 4.786452573038046),
    ("whisper-small.train", "sat_build_s", 0.25),
]


@pytest.mark.parametrize("cell,metric,value", PINNED)
def test_harness_readers_pinned_on_recorded_traces(cell, metric, value):
    tr = T.from_json((FIXTURES / f"trace_{cell}.json").read_text())
    assert _read(f"{metric}.x", _view(tr, cell)) == value
