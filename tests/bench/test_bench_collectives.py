"""The readers of the sharding layer (``collective_share``,
``tile_gather_share``) and the per-chip ``tile_share.decode4``, on a
synthetic trace of four chips whose shares are worked by hand."""
import json
import pathlib

import pytest

from chipbench import bench
from chipbench import program_trace as P
from chipbench import trace as T

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def synthetic():
    text = (FIXTURES / "trace_collectives_synthetic.json").read_text()
    return P.scoped(T.from_json(text), json.loads(text)["scopes"])


def _read(metric, trace):
    view = bench.View(trace, bench.config("mistral-nemo-12b")["model"],
                      bench.peaks("TPU v5 lite"), 0.25)
    return bench.metric_reader(metric).read(view)


# window [0, 1000] ns on four chips, busy 700, 500, 800 and 1000 (the last
# under a while that spans the window): 3000 in all. Collectives in the
# window: 200 (a gather, an all-reduce), 200 (async gather and
# all-reduce halves), 200 (a reduce-scatter as async-start/done, a
# permute) and 300 (an all-to-all, a gather; one more gather ends after
# the window): 900. Of them in the tile_gather scope: 100 on each chip.
# The tile kernel on chip 0 lies in the scope too but is no collective.
HAND = {"collective_share.decode4": 100 * 900 / 3000,
        "tile_gather_share.decode4": 100 * 400 / 3000,
        "tile_share.decode4": 100 * 100 / 3000}


@pytest.mark.parametrize("metric", sorted(HAND))
def test_sharding_readers_on_synthetic_trace(metric):
    assert _read(metric, synthetic()) == pytest.approx(HAND[metric],
                                                       rel=1e-12)


def test_collectives_by_opcode():
    tr = synthetic()
    names = {d: [o.kernel for o in ops if bench.metric_reader(
        "collective_share").is_collective(o)] for d, ops in tr.ops.items()}
    assert names["/device:TPU:1"] == ["all-gather-start", "all-gather-done",
                                      "all-reduce-start", "all-reduce-done"]
    assert names["/device:TPU:2"] == ["reduce-scatter-start",
                                      "reduce-scatter-done",
                                      "collective-permute"]
    assert "tile_rmsnorm" not in names["/device:TPU:0"]


def test_no_ops_or_no_collectives_read_none():
    tr = synthetic()
    empty = P.ProgramTrace({}, tr.spans, tr.window)
    assert _read("collective_share.decode4", empty) is None
    assert _read("tile_gather_share.decode4", empty) is None
    compute = P.ProgramTrace(
        {d: [o for o in ops if o.kernel.startswith("fusion")]
         for d, ops in tr.ops.items()}, tr.spans, tr.window)
    assert _read("collective_share.decode4", compute) is None
    unscoped = P.scoped(T.Trace(tr.ops, tr.spans, tr.window),
                        {d: [""] * len(ops) for d, ops in tr.ops.items()})
    assert _read("collective_share.decode4", unscoped) == pytest.approx(30.0)
    assert _read("tile_gather_share.decode4", unscoped) is None


def test_tile_share_sums_chips_where_decode4_averages():
    # the one-chip reader divides four chips' kernel time by one chip's
    # busy time
    assert _read("tile_share.decode", synthetic()) == pytest.approx(
        4 * HAND["tile_share.decode4"])
