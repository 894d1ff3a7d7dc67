"""PR-9 grid/block legality pass: mutation tests (one planted defect →
exactly one finding of exactly that code), the clean-suite zero-finding
sweep, dtype-aware row-block autosizing against the scoped VMEM limit,
and a property fuzz asserting that every certified (rows,
row_block) geometry executes bit-identically to the unblocked baseline."""
import dataclasses

import numpy as np

from _hypothesis_compat import given, settings, st
from repro.analysis.access import BlockAccess, GridModel
from repro.core import (KernelProgram, SaturatorConfig, VerifyConfig,
                        make_tile_op)
from repro.core.pallasgen import pick_row_block
from repro.core.telemetry import telemetry
from repro.kernels.tile_programs import PROGRAMS, get_tile_op
from repro.verify import (check_grid, check_tile_op, flash_attention_model,
                          ssd_scan_model, verify_tile_op)
from repro.verify.grid_check import check_tile_kernel_grid

RB, D = 8, 128


def _codes(res):
    return [f.code for f in res.findings]


# -- mutation 1: overlapping writes → grid-write-race -------------------------
def test_write_overlap_caught_exactly():
    """Grid of 3 over a 2-block output with an i%2 map: instances 0 and
    2 both own block 0 — a write-write race — while blocks 0 and 1 stay
    covered, so the race is the *only* finding."""
    m = GridModel(
        "mut_race", (3,),
        reads=(BlockAccess("x", "read", (RB, D), (3 * RB, D),
                           lambda i: (i, 0)),),
        writes=(BlockAccess("o", "write", (RB, D), (2 * RB, D),
                            lambda i: (i % 2, 0)),))
    res = check_grid(m)
    assert _codes(res) == ["grid-write-race"]
    assert not res.ok


# -- mutation 2: dropped remainder tile → grid-coverage-gap -------------------
def test_dropped_tile_caught_exactly():
    """Identity map but a grid one step short of the 3-block buffer:
    block 2 is never written."""
    m = GridModel(
        "mut_gap", (2,),
        reads=(),
        writes=(BlockAccess("o", "write", (RB, D), (3 * RB, D),
                            lambda i: (i, 0)),))
    res = check_grid(m)
    assert _codes(res) == ["grid-coverage-gap"]


# -- mutation 3: off-by-one index map → grid-oob-read -------------------------
def test_off_by_one_read_caught_exactly():
    """Read map shifted by one block: the last grid step reads block 3
    of a 3-block buffer. The (clean) write side must not double-report."""
    m = GridModel(
        "mut_oob", (3,),
        reads=(BlockAccess("x", "read", (RB, D), (3 * RB, D),
                           lambda i: (i + 1, 0)),),
        writes=(BlockAccess("o", "write", (RB, D), (3 * RB, D),
                            lambda i: (i, 0)),))
    res = check_grid(m)
    assert _codes(res) == ["grid-oob-read"]


# -- mutation 4: oversized block → grid-vmem-overflow -------------------------
def test_vmem_overflow_caught_exactly():
    """A (4096, 4096) f32 block read + written is 2 x 64 MiB — past the
    whole chip VMEM. The drift warning is suppressed when the hard
    overflow fires, so the error is the only finding."""
    big = (4096, 4096)
    m = GridModel(
        "mut_vmem", (1,),
        reads=(BlockAccess("x", "read", big, big, lambda i: (0, 0)),),
        writes=(BlockAccess("o", "write", big, big, lambda i: (0, 0)),))
    res = check_grid(m)
    assert _codes(res) == ["grid-vmem-overflow"]


# -- clean suite: zero findings ----------------------------------------------
def test_all_tile_kernels_certify_clean():
    for name in PROGRAMS:
        res = check_tile_op(get_tile_op(name))
        assert res.findings == [], \
            f"{name}: {[str(f) for f in res.findings]}"
        assert res.provable and res.grids_checked == 1


def test_handwritten_layouts_certify_clean():
    """The flash-attention and SSD-scan BlockSpec layouts — including
    the inert kv axis on flash's output map (a legal revisit the race
    detector must not flag)."""
    for model in (flash_attention_model(2, 4, 2, 512, 128),
                  ssd_scan_model(2, 4, 512, 64, 128)):
        res = check_grid(model)
        assert res.findings == [], [str(f) for f in res.findings]
        assert res.vmem_bytes > 0


# -- satellite 1+2: declared-geometry, dtype-aware autosizing -----------------
def _wide_prog(name, dtype):
    """4 in + 3 out at d=1024: 9 tiles, so a 512 row block is estimated
    at 512*1024*9*(2*4+4) B = 54 MiB in f32 — past the 48 MiB scoped
    limit — but 36 MiB in bf16."""
    p = KernelProgram(name, dtype=dtype)
    a = p.array_in("a", shape=(8, 1024), dtype=dtype)
    b = p.array_in("b", shape=(8, 1024), dtype=dtype)
    c_ = p.array_in("c", shape=(8, 1024), dtype=dtype)
    d_ = p.array_in("d", shape=(8, 1024), dtype=dtype)
    p.array_out("o1", shape=(8, 1024), dtype=dtype)
    p.array_out("o2", shape=(8, 1024), dtype=dtype)
    p.array_out("o3", shape=(8, 1024), dtype=dtype)
    av, bv, cv, dv = a.load(), b.load(), c_.load(), d_.load()
    p.store("o1", av * bv + cv)
    p.store("o2", av + dv)
    p.store("o3", bv * dv)
    return p


def test_pick_row_block_is_dtype_aware():
    assert pick_row_block(1024, 9, 4) == 256    # f32 at d=1024 halves
    assert pick_row_block(1024, 9, 2) == 512    # bf16 affords the default
    assert pick_row_block(128, 7, 4) == 512     # the model kernels' case


def test_d1024_program_autosizes_smaller_block():
    """A d=1024 f32 call must get the VMEM-fitting 256, not the blanket
    512 — and its certified exact footprint must fit the scoped limit."""
    op = make_tile_op(_wide_prog("wide1024_f32", "f32"))
    assert op.row_block is None               # sized per call
    assert op.block_rows(1024, 4) == 256
    res = check_tile_op(op)
    assert [f for f in res.findings if f.severity == "error"] == []


def test_d1024_bf16_program_keeps_large_block():
    op = make_tile_op(_wide_prog("wide1024_bf16", "bf16"))
    assert op.block_rows(1024, 2) == 512


def test_vmem_pass_budgets_the_scoped_limit():
    """The grid pass certifies against the same scoped limit the picker
    sizes for: at row_block=1024 the wide f32 program's double-buffered
    blocks (1024*1024*4B*2*7 = 56 MiB) overflow the 48 MiB limit — one
    error — while the picked 256 certifies clean."""
    op = make_tile_op(_wide_prog("wide1024_limit", "f32"))
    res = check_tile_kernel_grid(op.pk, op.sk.ssa.prog, row_block=1024)
    assert _codes(res) == ["grid-vmem-overflow"]
    assert "limit 50331648 B" in res.findings[0].message
    res = check_tile_kernel_grid(op.pk, op.sk.ssa.prog)
    assert res.findings == [] and res.vmem_bytes <= 48 * 1024 ** 2


# -- wiring: make_tile_op + telemetry -----------------------------------------
def test_make_tile_op_verify_wiring_counts_grids():
    before = telemetry().snapshot()["verify"]["grids_checked"]
    op = make_tile_op(_wide_prog("wide1024_wired", "f32"),
                      SaturatorConfig(mode="accsat",
                                      verify_cfg=VerifyConfig("cheap")))
    after = telemetry().snapshot()["verify"]["grids_checked"]
    assert after == before + 1
    assert verify_tile_op(op).grids_checked == 1


# -- property fuzz: certified geometry == unblocked execution ----------------
def _swiglu_op():
    if not hasattr(_swiglu_op, "_op"):
        _swiglu_op._op = get_tile_op("swiglu")
    return _swiglu_op._op


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=1, max_value=48),
       st.integers(min_value=1, max_value=48))
def test_certified_blockings_are_bit_identical(rows, rb_raw):
    """Any (rows, row_block) the grid pass certifies error-free must
    execute bit-identically to row_block=rows (one tile, no padding
    path): coverage + disjointness + bounds together are exactly the
    property that blocking cannot change results."""
    rb = min(rb_raw, rows)
    base = _swiglu_op()
    res = check_tile_kernel_grid(base.pk, base.sk.ssa.prog,
                                 row_block=rb, rows=rows)
    assert [f for f in res.findings if f.severity == "error"] == [], \
        [str(f) for f in res.findings]
    rng = np.random.default_rng(rows * 49 + rb)
    a = rng.uniform(0.1, 1.0, size=(rows, 128)).astype(np.float32)
    b = rng.uniform(0.1, 1.0, size=(rows, 128)).astype(np.float32)
    blocked = dataclasses.replace(base, row_block=rb)
    unblocked = dataclasses.replace(base, row_block=rows)
    out_b = np.asarray(blocked.apply(a, b))
    out_u = np.asarray(unblocked.apply(a, b))
    np.testing.assert_array_equal(out_b, out_u)
