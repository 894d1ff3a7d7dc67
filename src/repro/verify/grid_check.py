"""Grid/BlockSpec legality certification — the ``grid`` pass (PR 9).

Every other :mod:`repro.verify` pass audits what happens *inside* one
grid instance (rules, e-graph, statement order, emitted source). This
pass certifies the launch configuration itself: given a
:class:`repro.analysis.access.GridModel` — grid extents plus each
operand's block shape, buffer shape and index map — it statically
proves, per kernel and per candidate configuration:

* **coverage** — every output block is written by exactly one grid
  instance (modulo *inert* axes: a grid axis the output map ignores,
  like flash attention's kv step, legally revisits the same block and
  is projected out first). A missing block — classically the dropped
  remainder tile when ``rows % row_block != 0`` — is
  ``grid-coverage-gap``.
* **disjointness** — no two effective instances write the same output
  block: ``grid-write-race``, the repo's first cross-instance race
  detector.
* **bounds** — no block index escapes the buffer's block lattice
  (``grid-oob-read`` / ``grid-oob-write``). Buffer shapes are
  *post-padding* (``_ceil_to``), so the pad region is modeled as
  in-bounds explicitly rather than waved at.
* **VMEM budget** — the exact working set (block windows × double-buffer
  multiplicity + scratch) fits ``chip.vmem_limit_bytes``, the scoped
  VMEM limit the tile kernels request from the compiler and the budget
  ``pick_row_block`` sizes against: ``grid-vmem-overflow``.

Certification is exact set arithmetic when the grid is enumerable
(≤ ``ENUM_LIMIT`` instances — every committed kernel) and falls back to
an affine bijection proof for larger grids; configurations that are
neither enumerable nor affine get corner-sampled bounds plus a
``grid-unprovable`` warning (see docs/verification.md for what is and
is not provable).

Consumers: ``verify_tile_op`` (the ``verify=`` wiring in
``make_tile_op``), the grid-audit stage of ``benchmarks/verify_sweep.py``
(13 tile kernels × schedules + the hand-written
flash-attention / SSD-scan layouts), and the static legality pre-filter
of ``benchmarks/tune.py``.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.access import (ENUM_LIMIT, BlockAccess, GridModel,
                                   IndexMapSummary, affine_bounds,
                                   eval_index, summarize_index_map)
from repro.core.hardware import DEFAULT_CHIP
from .findings import PASS_GRID, Finding

# Coverage lattices larger than this are not materialized even when the
# grid itself is enumerable (a sparse map over a huge buffer): the gap
# check degrades to the unprovable warning instead of an OOM.
_LATTICE_LIMIT = 4 * ENUM_LIMIT
# Corner-sampling cap for the non-enumerable, non-affine fallback.
_CORNER_LIMIT = 1 << 12


@dataclasses.dataclass
class GridCheckResult:
    """Findings + coverage facts of one grid certification."""
    findings: List[Finding] = dataclasses.field(default_factory=list)
    grids_checked: int = 1
    vmem_bytes: int = 0
    provable: bool = True     # False: fell back to sampling somewhere

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def ok(self) -> bool:
        return not self.errors()


def _f(sev: str, code: str, subject: str, msg: str) -> Finding:
    return Finding(PASS_GRID, sev, code, msg, subject)


def _oob_code(acc: BlockAccess) -> str:
    return "grid-oob-read" if acc.mode == "read" else "grid-oob-write"


def _fmt_env(env: Sequence[int]) -> str:
    return "(" + ", ".join(str(e) for e in env) + ")"


# ---------------------------------------------------------------------------
# Exhaustive certification (the path every committed kernel takes)
# ---------------------------------------------------------------------------
def _certify_enum(model: GridModel, acc: BlockAccess,
                  summ: IndexMapSummary,
                  envs: List[Tuple[int, ...]],
                  findings: List[Finding]) -> None:
    subject = f"{model.name}:{acc.array}"
    nb = acc.n_blocks()
    touch: Dict[Tuple[int, ...], Tuple[int, ...]] = {}
    for env in envs:
        blk = eval_index(summ, env)
        if len(blk) != len(nb):
            findings.append(_f(
                "error", "grid-rank-mismatch", subject,
                f"index map returned rank {len(blk)} for a rank-"
                f"{len(nb)} operand"))
            return
        touch[env] = blk

    oob = [(env, blk) for env, blk in touch.items()
           if any(not (0 <= b < n) for b, n in zip(blk, nb))]
    if oob:
        env, blk = oob[0]
        findings.append(_f(
            "error", _oob_code(acc), subject,
            f"{len(oob)}/{len(envs)} grid instances index outside the "
            f"{nb} block lattice (e.g. instance {_fmt_env(env)} -> block "
            f"{blk}); buffer {acc.array_shape}, block {acc.block_shape}"))
        return   # bounds broke — coverage/race verdicts would only cascade
    if acc.mode == "read":
        return

    # inert axes: varying the axis never moves this write's footprint —
    # a legal revisit (flash attention's kv sweep), not a race candidate
    n_axes = len(model.grid)
    inert = []
    for k in range(n_axes):
        base = {env: touch[env[:k] + (0,) + env[k + 1:]] for env in envs}
        if all(touch[env] == base[env] for env in envs):
            inert.append(k)
    used = [k for k in range(n_axes) if k not in inert]

    seen: Dict[Tuple[int, ...], Tuple[int, ...]] = {}   # block -> eff env
    races = []
    for env in envs:
        eff = tuple(env[k] for k in used)
        blk = touch[env]
        prev = seen.get(blk)
        if prev is None:
            seen[blk] = eff
        elif prev != eff:
            races.append((prev, eff, blk))
    if races:
        a, b, blk = races[0]
        findings.append(_f(
            "error", "grid-write-race", subject,
            f"{len(races)} write-write collision(s) across grid "
            f"instances (e.g. instances {_fmt_env(a)} and {_fmt_env(b)} "
            f"of the non-inert axes {used} both write block {blk})"))
        return   # the colliding map also double-covers; don't double-report

    import math
    lattice = math.prod(nb)
    if lattice > _LATTICE_LIMIT:
        findings.append(_f(
            "warning", "grid-unprovable", subject,
            f"coverage lattice {nb} too large to materialize "
            f"({lattice} blocks > {_LATTICE_LIMIT}); gap check skipped"))
        return
    missing = [blk for blk in itertools.product(*[range(n) for n in nb])
               if blk not in seen]
    if missing:
        findings.append(_f(
            "error", "grid-coverage-gap", subject,
            f"{len(missing)}/{lattice} output block(s) written by no "
            f"grid instance (e.g. block {missing[0]}); grid "
            f"{model.grid}, block {acc.block_shape}, buffer "
            f"{acc.array_shape}"))


# ---------------------------------------------------------------------------
# Affine certification (grids too large to enumerate)
# ---------------------------------------------------------------------------
def _certify_affine(model: GridModel, acc: BlockAccess,
                    summ: IndexMapSummary,
                    findings: List[Finding]) -> bool:
    """True when the access was fully certified without enumeration."""
    if not summ.fully_affine:
        return False
    subject = f"{model.name}:{acc.array}"
    nb = acc.n_blocks()
    dims = summ.dims or []
    if len(dims) != len(nb):
        findings.append(_f(
            "error", "grid-rank-mismatch", subject,
            f"index map returns rank {len(dims)} for a rank-{len(nb)} "
            "operand"))
        return True
    oob_dims = []
    for j, (sym, n) in enumerate(zip(dims, nb)):
        lo, hi = affine_bounds(sym, model.grid)
        if lo < 0 or hi >= n:
            oob_dims.append((j, lo, hi, n))
    if oob_dims:
        j, lo, hi, n = oob_dims[0]
        findings.append(_f(
            "error", _oob_code(acc), subject,
            f"affine block index range [{lo}, {hi}] escapes "
            f"[0, {n}) along dim {j} (block lattice {nb})"))
        return True
    if acc.mode == "read":
        return True

    # bijection proof for the write: each non-inert grid axis must drive
    # exactly one output dim with unit coefficient and zero offset, each
    # output dim at most one axis, and extents must match — then the map
    # is a coordinate embedding: injective (no race) and surjective onto
    # the lattice (no gap)
    used_axes = sorted({k for sym in dims
                        for k, c in enumerate(sym.affine[0]) if c})
    axis_dims: Dict[int, int] = {}
    ok = True
    for j, sym in enumerate(dims):
        coeffs, const = sym.affine
        nz = [(k, c) for k, c in enumerate(coeffs) if c]
        if len(nz) > 1:
            ok = False
            break
        if not nz:
            if const != 0 or nb[j] != 1:
                ok = False
                break
            continue
        k, c = nz[0]
        if c != 1 or const != 0 or k in axis_dims \
                or model.grid[k] != nb[j]:
            ok = False
            break
        axis_dims[k] = j
    if ok and sorted(axis_dims) == used_axes:
        return True
    findings.append(_f(
        "warning", "grid-unprovable", subject,
        f"write map over {model.n_instances} instances is affine but "
        "not a unit coordinate embedding; coverage/disjointness not "
        "proven (bounds were)"))
    return True


def _corner_envs(grid: Sequence[int]) -> List[Tuple[int, ...]]:
    corners = itertools.product(*[(0, g - 1) if g > 1 else (0,)
                                  for g in grid])
    return list(itertools.islice(corners, _CORNER_LIMIT))


# ---------------------------------------------------------------------------
# The checker
# ---------------------------------------------------------------------------
def check_grid(model: GridModel, chip=DEFAULT_CHIP) -> GridCheckResult:
    """Certify one launch configuration; see the module docstring for
    the verdict semantics. Error severities gate CI; warnings mark the
    honestly-unprovable remainder."""
    findings: List[Finding] = []
    provable = True
    n_axes = len(model.grid)
    summaries = [(acc, summarize_index_map(acc.index_map, n_axes))
                 for acc in model.reads + model.writes]
    if model.n_instances <= ENUM_LIMIT:
        envs = list(model.instances())
        for acc, summ in summaries:
            _certify_enum(model, acc, summ, envs, findings)
    else:
        for acc, summ in summaries:
            if _certify_affine(model, acc, summ, findings):
                continue
            provable = False
            subject = f"{model.name}:{acc.array}"
            nb = acc.n_blocks()
            bad = []
            for env in _corner_envs(model.grid):
                try:
                    blk = eval_index(summ, env)
                except Exception:
                    continue
                if len(blk) == len(nb) and any(
                        not (0 <= b < n) for b, n in zip(blk, nb)):
                    bad.append((env, blk))
            if bad:
                env, blk = bad[0]
                findings.append(_f(
                    "error", _oob_code(acc), subject,
                    f"corner sample: instance {_fmt_env(env)} indexes "
                    f"block {blk} outside lattice {nb}"))
            findings.append(_f(
                "warning", "grid-unprovable", subject,
                f"non-affine index map over {model.n_instances} "
                f"instances (> {ENUM_LIMIT}): certified at grid-box "
                "corners only"))
    provable = provable and not any(f.code == "grid-unprovable"
                                    for f in findings)

    vb = model.vmem_bytes
    if vb > chip.vmem_limit_bytes:
        findings.append(_f(
            "error", "grid-vmem-overflow", model.name,
            f"exact VMEM working set {vb} B (blocks x double-buffers + "
            f"scratch) exceeds the scoped VMEM limit "
            f"{chip.vmem_limit_bytes} B"))
    return GridCheckResult(findings=findings, grids_checked=1,
                           vmem_bytes=vb, provable=provable)


# ---------------------------------------------------------------------------
# Model builders: TileOp, flash attention, SSD scan
# ---------------------------------------------------------------------------
def _is_bcast_spec(spec) -> bool:
    """Declared broadcast row (leading extent 1, all dims known) — the
    runtime analogue is ``prod(shape[:-1]) != rows`` in plan_tile_call."""
    shape = getattr(spec, "shape", None)
    if not shape or any(s is None for s in shape):
        return False
    import math
    return math.prod(shape[:-1]) == 1 if len(shape) > 1 else True


def tile_input_shapes(pk, prog, rows: int, d: int) -> List[Tuple[int, ...]]:
    """Synthetic operand shapes for one audit configuration: row-tiled
    arrays get ``(rows, d)``, declared broadcast rows ``(1, d)`` — the
    geometry ``measure.py``'s inputs take after ``_apply_tile_op``'s
    reshape, scaled to the audited feature width."""
    shapes: List[Tuple[int, ...]] = []
    for name in pk.in_arrays:
        spec = prog.arrays.get(name) if prog is not None else None
        shapes.append((1, d) if spec is not None and _is_bcast_spec(spec)
                      else (rows, d))
    return shapes


def tile_call_model(pk, plan, dtype_bytes: int = 4) -> GridModel:
    """Convert one :func:`repro.core.pallasgen.plan_tile_call` plan into
    the checkable :class:`GridModel`. Every blocked operand is
    double-buffered."""
    reads = tuple(
        BlockAccess(e.name, "read", e.block_shape, e.buffer_shape,
                    e.index_map, dtype_bytes=dtype_bytes)
        for e in plan.inputs)
    writes = tuple(
        BlockAccess(e.name, "write", e.block_shape, e.buffer_shape,
                    e.index_map, dtype_bytes=dtype_bytes)
        for e in plan.outputs)
    return GridModel(pk.name, plan.grid, reads, writes)


def check_tile_kernel_grid(pk, prog, row_block: Optional[int] = None,
                           rows: Optional[int] = None,
                           d: Optional[int] = None,
                           chip=DEFAULT_CHIP) -> GridCheckResult:
    """Certify one emitted :class:`~repro.core.pallasgen.PallasKernel`'s
    launch plan at a given ``row_block`` (default: what
    ``pick_row_block`` sizes for the audited width and declared dtype).

    ``rows`` defaults to a geometry that exercises the padded remainder
    tile (``rows % row_block != 0``); ``d`` to the program's declared
    feature width."""
    from repro.core.pallasgen import (_declared_dtype_bytes,
                                      _declared_feature_dim,
                                      pick_row_block, plan_tile_call)
    if d is None:
        d = (_declared_feature_dim(prog) if prog is not None else None) \
            or 256
    dtype_bytes = _declared_dtype_bytes(prog) if prog is not None else 4
    n_tiles = len(pk.in_arrays) + len(pk.out_arrays) + 2
    rb = row_block or pick_row_block(d, n_tiles, dtype_bytes, chip=chip)
    if rows is None:
        rows = 2 * rb + max(1, rb // 2)   # forces a ragged remainder tile
    plan = plan_tile_call(pk, tile_input_shapes(pk, prog, rows, d), rb)
    return check_grid(tile_call_model(pk, plan, dtype_bytes=dtype_bytes),
                      chip)


def check_tile_op(op, rows: Optional[int] = None, d: Optional[int] = None,
                  row_block: Optional[int] = None,
                  chip=DEFAULT_CHIP) -> GridCheckResult:
    """Certify one :class:`~repro.core.pallasgen.TileOp` configuration —
    :func:`check_tile_kernel_grid` at the op's own ``row_block`` (or an
    explicit candidate, which is how ``benchmarks/tune.py`` pre-filters
    its search space)."""
    prog = op.sk.ssa.prog if getattr(op, "sk", None) is not None else None
    return check_tile_kernel_grid(op.pk, prog,
                                  row_block=row_block or op.row_block,
                                  rows=rows, d=d, chip=chip)


def flash_attention_model(B: int, H: int, KH: int, S: int, D: int,
                          q_block: Optional[int] = None,
                          kv_block: Optional[int] = None,
                          dtype_bytes: int = 4) -> GridModel:
    """The hand-written flash-attention launch as a checkable model
    (shared layout: :func:`repro.kernels.flash_attention.attention_layout`).
    Tiles left as ``None`` are the kernel's own choice,
    :func:`~repro.kernels.flash_attention.attention_tiles` of S."""
    from repro.kernels.flash_attention import attention_layout, \
        attention_tiles
    lay = attention_layout(B, H, KH, S, D,
                           min(q_block or attention_tiles(S), S),
                           min(kv_block or attention_tiles(S), S))
    reads = tuple(BlockAccess(n, "read", *lay[n], dtype_bytes=dtype_bytes)
                  for n in ("q", "k", "v"))
    writes = (BlockAccess("o", "write", *lay["o"], dtype_bytes=dtype_bytes),
              BlockAccess("lse", "write", *lay["lse"], dtype_bytes=4))
    return GridModel("flash_attention", lay["grid"], reads, writes,
                     scratch_bytes=lay["scratch_bytes"])


def ssd_scan_model(B: int, H: int, S: int, P: int, N: int,
                   chunk: int = 128,
                   dtype_bytes: int = 4) -> GridModel:
    """The hand-written SSD-scan launch as a checkable model (shared
    layout: :func:`repro.kernels.ssd_scan.ssd_layout`)."""
    from repro.kernels.ssd_scan import ssd_layout
    lay = ssd_layout(B * H, S, P, N, min(chunk, S))
    reads = tuple(BlockAccess(n, "read", *lay[n], dtype_bytes=dtype_bytes)
                  for n in ("x", "dt", "a_log", "b", "c", "d_skip"))
    writes = (BlockAccess("o", "write", *lay["o"],
                          dtype_bytes=dtype_bytes),)
    return GridModel("ssd_scan", lay["grid"], reads, writes,
                     scratch_bytes=lay["scratch_bytes"])
