"""Pallas/TPU code generation for saturated tile programs (paper §VI on TPU).

A *tile program* is a straight-line :class:`KernelProgram` over whole-tile
arrays (every load/store is un-indexed). The generator reuses the JAX
scheduler in :mod:`repro.core.codegen` — including **bulk load** — but
emits a Pallas kernel body where:

* whole-tile loads become ``ref[...]`` VMEM reads. With ``bulk=True`` every
  read is issued before the first compute op (sorted by array name), which
  on TPU front-loads the HBM→VMEM traffic exactly like the paper's
  bulk-load front-loads global-memory requests on the GPU;
* whole-tile stores become ``out_ref[...] = value``;
* the surrounding ``pl.pallas_call`` tiles the leading (row) dimension with
  an explicit BlockSpec, keeping the working set inside VMEM and the lane
  dimension a multiple of 128.

The companion ``make_tile_op`` wrapper builds a jitted op that reshapes
``(..., d)`` operands into rows, runs the kernel over a 1-D grid, and
reshapes back. On CPU it runs in interpret mode (kernel body executed in
Python) — bit-identical semantics, used by all tests.

The generator is the registry's ``"pallas"`` emitter (see
:mod:`repro.core.emit`), :class:`SyncPallasGenerator` — known as
``PallasGenerator`` before the registry. Compiled Pallas double-buffers
every blocked operand across grid steps on its own, so the HBM→VMEM
copy of the next row block overlaps the current one's compute.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import chaos

from .codegen import JaxCodeGenerator, GenStats, _PRELUDE
from .dsl import KernelProgram
from .pipeline import SaturatorConfig, saturate_program
from .ssa import LoopRegion, Region, StoreEffect
from .hardware import DEFAULT_CHIP

# Kernel bodies compute in f32 whatever the operands store: every load is
# upcast and every store cast back to its ref's dtype (bf16 refs take
# neither f32 values nor f32 scalar broadcasts on the TPU).
_UPCAST = ".astype(jnp.float32)"


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@dataclasses.dataclass
class PallasKernel:
    name: str
    source: str
    kernel_body: Callable      # fn(*in_refs, *out_refs) with scalars closed over
    in_arrays: List[str]       # tile inputs (order of pallas_call operands)
    weight_arrays: List[str]   # rank-deficient inputs broadcast over rows
    out_arrays: List[str]
    scalars: List[str]
    stats: GenStats
    bulk: bool
    schedule_mode: str = "bulk"
    schedule: Optional[Any] = None   # ScheduleResult for explicit orders
    emitter: str = "pallas"          # registry name that produced this


class SyncPallasGenerator(JaxCodeGenerator):
    """The ``"pallas"`` emitter: a synchronous Pallas kernel body instead
    of a jnp function. Known as ``PallasGenerator`` before the PR-8
    emitter registry (:mod:`repro.core.emit`); that name remains as a
    deprecated alias."""

    def _check_tilable(self):
        def walk(region: Region):
            for item in region.items:
                if isinstance(item, LoopRegion):
                    raise ValueError(
                        "Pallas tile programs must be straight-line; "
                        f"kernel {self.ssa.prog.name!r} has a for-loop "
                        "(use the JAX generator or lift the loop to the grid)")
                if item.index_cids:
                    raise ValueError(
                        "Pallas tile programs use whole-tile stores; "
                        f"kernel {self.ssa.prog.name!r} stores with indices")
        walk(self.ssa.region)
        for cid, n in list(self.choice.items()):
            if n.op == "load" and len(n.children) > 1:
                raise ValueError("Pallas tile programs use whole-tile loads")
            if n.op == "call":
                raise ValueError("calls not supported in Pallas tile programs")

    # loads read refs --------------------------------------------------------
    def emit_value(self, cid: int, lines: List[str], indent: str) -> str:
        cid = self.eg.find(cid)
        memo_ok = (self.reuse_temps is True
                   or (self.reuse_temps in (False, "lets")
                       and cid in self._let_set))
        bound = self.scope.get(cid, memo=memo_ok)
        if bound is not None:
            return bound
        n = self.node(cid)
        if n.op == "load":
            arr = self.emit_value(n.children[0], lines, indent)
            name = self._fresh()
            self.stats.n_temps += 1
            self.stats.n_loads += 1
            self.stats.instruction_mix["load"] = \
                self.stats.instruction_mix.get("load", 0) + 1
            lines.append(f"{indent}{name} = {arr}[...]{_UPCAST}")
            self.scope.bind(cid, name)
            return name
        return super().emit_value(cid, lines, indent)

    def _emit_store(self, eff: StoreEffect, lines: List[str], indent: str):
        val = self.emit_value(eff.value_cid, lines, indent)
        dst_ref = f"{eff.array}_oref"
        if eff.pred_cid is not None:
            pred = self.emit_value(eff.pred_cid, lines, indent)
            src = self.scope.get_sym(eff.version_in)
            old = f"{src}[...]" if src else f"{dst_ref}[...]"
            val = f"jnp.where({pred}, {val}, {old}{_UPCAST})"
        lines.append(
            f"{indent}{dst_ref}[...] = ({val}).astype({dst_ref}.dtype)")
        # later loads of this array read the ref we just wrote
        self.scope.bind_sym(eff.version_out, dst_ref)
        self.stats.n_stores += 1

    def generate_pallas(self) -> PallasKernel:
        self._check_tilable()
        prog = self.ssa.prog
        in_arrays = [a.name for a in prog.arrays.values()
                     if a.role in ("in", "inout")]
        out_arrays = [a.name for a in prog.arrays.values()
                      if a.role in ("out", "inout")]
        scalars = list(prog.scalars)
        ref_params = ([f"{n}_ref" for n in in_arrays]
                      + [f"{n}_oref" for n in out_arrays])
        lines: List[str] = []
        indent = "    "
        for a in in_arrays:
            self.scope.bind_sym(f"{a}@0", f"{a}_ref")
        for a in out_arrays:
            self.scope.bind_sym(f"{a}@undef", f"{a}_oref")
        sched = self._resolve_schedule()
        if sched is None and self.bulk:
            self._collect_load_regions()
        self.emit_region(self.ssa.region, (), lines, indent)
        body = "\n".join(lines) if lines else "    pass"
        sig = ", ".join(ref_params + scalars)
        src = (f"{_PRELUDE}\n"
               f"def {self.fn_name}_body({sig}):\n{body}\n")
        glb: Dict[str, Any] = {}
        chaos.maybe_raise("exec_fail", prog.name,
                          "generated Pallas source")
        exec(compile(src, f"<pallas:{self.fn_name}>", "exec"), glb)
        return PallasKernel(
            name=self.fn_name, source=src,
            kernel_body=glb[f"{self.fn_name}_body"],
            in_arrays=in_arrays, weight_arrays=[], out_arrays=out_arrays,
            scalars=scalars, stats=self.stats, bulk=self.bulk,
            schedule_mode=self.schedule_mode, schedule=sched)


class PallasGenerator(SyncPallasGenerator):
    """Deprecated alias of :class:`SyncPallasGenerator`.

    Use ``repro.core.emit.get_emitter("pallas")`` (or
    ``SyncPallasGenerator`` directly) instead; this name is kept so
    pre-PR-8 imports keep working."""

    def __init__(self, *args, **kwargs):
        warnings.warn(
            "repro.core.pallasgen.PallasGenerator is deprecated; use "
            "repro.core.emit.get_emitter('pallas') or SyncPallasGenerator",
            DeprecationWarning, stacklevel=2)
        super().__init__(*args, **kwargs)


@dataclasses.dataclass
class TileOp:
    """Jitted op wrapping a saturated Pallas kernel over a row grid.

    ``pk=None`` marks a degraded op (Pallas emission failed under the
    guarded runtime): ``apply`` then delegates to ``jax_ref`` — the
    kernel still runs, one ladder rung down (see docs/robustness.md).

    ``row_block=None`` sizes the row block per call from the operands
    that arrive (:meth:`block_rows`); an int pins it (the tuner's
    candidates, tests)."""
    name: str
    pk: Optional[PallasKernel]
    jax_ref: Callable          # pure-jnp oracle built from the same program
    row_block: Optional[int]
    source: str
    # full pipeline result the kernel was generated from — the timing/
    # calibration harness (benchmarks/measure.py) extracts its feature
    # vector from this exact extraction choice
    sk: Optional[Any] = None

    @property
    def n_tiles(self) -> int:
        """Tiles one grid step holds: every operand plus two temporaries."""
        return len(self.pk.in_arrays) + len(self.pk.out_arrays) + 2

    def block_rows(self, d: int, dtype_bytes: int, chip=DEFAULT_CHIP) -> int:
        """Row block for operands of feature width ``d`` stored at
        ``dtype_bytes`` (the pinned ``row_block`` when one is set)."""
        return self.row_block or pick_row_block(d, self.n_tiles,
                                                dtype_bytes, chip)

    def __call__(self, *arrays, interpret: Optional[bool] = None, **scalars):
        return self.apply(*arrays, interpret=interpret, **scalars)

    def apply(self, *arrays, interpret: Optional[bool] = None, **scalars):
        """Run the Pallas kernel. Differentiable: the backward pass is
        ``jax.vjp`` of ``jax_ref``, the same math in jnp."""
        if self.pk is None:
            return self.jax_ref(*arrays, **scalars)
        interpret = _on_cpu() if interpret is None else interpret
        names = tuple(sorted(scalars))

        def ref(arrs, vals):
            return self.jax_ref(*arrs, **dict(zip(names, vals)))

        @jax.custom_vjp
        def op(arrs, vals):
            return _apply_tile_op(self, arrs, tuple(zip(names, vals)),
                                  interpret)

        def fwd(arrs, vals):
            return op(arrs, vals), (arrs, vals)

        def bwd(res, ct):
            return jax.vjp(ref, *res)[1](ct)

        op.defvjp(fwd, bwd)
        vals = tuple(jnp.asarray(scalars[n], jnp.float32) for n in names)
        return op(tuple(arrays), vals)


def _row_index_map(i):
    """Row-tiled operand: grid step ``i`` owns row-block ``i``."""
    return (i, 0)


def _bcast_index_map(i):
    """Broadcast weight row: every grid step reads block (0, 0)."""
    return (0, 0)


@dataclasses.dataclass(frozen=True)
class TileEntry:
    """One operand of a planned tile-op ``pallas_call``."""
    name: str
    kind: str                            # "row" | "bcast"
    block_shape: Tuple[int, int]
    buffer_shape: Tuple[int, int]        # post-pad 2-D operand shape
    index_map: Callable


@dataclasses.dataclass(frozen=True)
class TileCallPlan:
    """The launch geometry of one tile-op call: grid, per-operand block
    shapes, buffer shapes (post-``_ceil_to`` padding) and index maps.

    Built by :func:`plan_tile_call` and consumed by *both* the runtime
    (``_apply_tile_op`` constructs its BlockSpecs from it) and the
    static verifier (``repro.verify.grid_check`` certifies exactly this
    plan) — one source of truth, so what is certified is what runs."""
    rows: int
    d: int
    row_block: int
    padded: int
    grid: Tuple[int, ...]
    inputs: Tuple[TileEntry, ...]
    outputs: Tuple[TileEntry, ...]


def plan_tile_call(pk: PallasKernel, in_shapes: Sequence[Tuple[int, ...]],
                   row_block: int) -> TileCallPlan:
    """Plan the grid/BlockSpec layout for ``pk`` over operands of the
    given (pre-reshape) shapes. Inputs whose leading extents multiply to
    the lead operand's row count tile over rows; anything else is a
    broadcast weight row re-read by every grid step."""
    lead = tuple(in_shapes[0])
    d = lead[-1]
    rows = math.prod(lead[:-1]) if len(lead) > 1 else 1
    rb = min(row_block, rows)
    padded = _ceil_to(rows, rb)
    inputs = []
    for name, shp in zip(pk.in_arrays, in_shapes):
        if len(shp) >= 2 and math.prod(shp[:-1]) == rows:
            inputs.append(TileEntry(name, "row", (rb, shp[-1]),
                                    (padded, shp[-1]), _row_index_map))
        else:
            w = math.prod(shp)
            inputs.append(TileEntry(name, "bcast", (1, w), (1, w),
                                    _bcast_index_map))
    outputs = tuple(TileEntry(name, "row", (rb, d), (padded, d),
                              _row_index_map) for name in pk.out_arrays)
    return TileCallPlan(rows=rows, d=d, row_block=rb, padded=padded,
                        grid=(padded // rb,), inputs=tuple(inputs),
                        outputs=outputs)


def _apply_tile_op(op: TileOp, arrays, scalar_items, interpret: bool):
    pk = op.pk
    lead = arrays[0]
    d = max(a.shape[-1] for a in arrays)
    rb = op.block_rows(d, max(jnp.dtype(a.dtype).itemsize for a in arrays))
    plan = plan_tile_call(pk, [a.shape for a in arrays], rb)
    rows, padded, d = plan.rows, plan.padded, plan.d
    ins2d = []
    for e, a in zip(plan.inputs, arrays):
        if e.kind == "row":
            a2 = a.reshape(rows, a.shape[-1])
            if padded != rows:
                a2 = jnp.pad(a2, ((0, padded - rows), (0, 0)))
        else:  # broadcast weight (g, b, ...) — same block every row-tile
            a2 = a.reshape(1, -1)
        ins2d.append(a2)
    in_specs = [pl.BlockSpec(e.block_shape, e.index_map)
                for e in plan.inputs]
    # scalars (eps, lr, ...) travel as one f32 SMEM operand after the
    # tiles, so traced values work inside jit
    names = [n for n, _ in scalar_items]
    n_in = len(ins2d)
    if names:
        ins2d.append(jnp.stack([jnp.asarray(v, jnp.float32)
                                for _, v in scalar_items]))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    def body(*refs):
        if not names:
            return pk.kernel_body(*refs)
        s_ref = refs[n_in]
        pk.kernel_body(*refs[:n_in], *refs[n_in + 1:],
                **{n: s_ref[i] for i, n in enumerate(names)})

    out_specs = [pl.BlockSpec(e.block_shape, e.index_map)
                 for e in plan.outputs]
    out_shapes = [jax.ShapeDtypeStruct(e.buffer_shape, lead.dtype)
                  for e in plan.outputs]
    call = pl.pallas_call(
        body, grid=plan.grid, in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shapes if len(out_shapes) > 1 else out_shapes[0],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=DEFAULT_CHIP.vmem_limit_bytes),
        name=f"tile_{op.name}", interpret=interpret)
    outs = call(*ins2d)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    outs = [o[:rows].reshape(lead.shape[:-1] + (d,)) for o in outs]
    return outs[0] if len(outs) == 1 else tuple(outs)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def vmem_estimate(row_block: int, d: int, n_tiles: int,
                  dtype_bytes: int = 4) -> int:
    """VMEM one grid step of a tile kernel may hold: each of ``n_tiles``
    ``(row_block, d)`` tiles twice at ``dtype_bytes`` (Pallas
    double-buffers every blocked operand across grid steps) plus once
    as the f32 value the body computes on. Conservative — broadcast rows
    count as full tiles; ``repro.verify.grid_check`` holds the exact
    double-buffered block footprint."""
    return row_block * d * n_tiles * (2 * dtype_bytes + 4)


def pick_row_block(d: int, n_tiles: int, dtype_bytes: int = 4,
                   chip=DEFAULT_CHIP) -> int:
    """Largest power-of-two row block (8..512) whose :func:`vmem_estimate`
    fits ``chip.vmem_limit_bytes`` — the scoped-VMEM limit each tile
    kernel requests from the compiler, and the budget the grid
    verifier certifies against."""
    blk = 512
    while blk > 8 and (vmem_estimate(blk, d, n_tiles, dtype_bytes)
                       > chip.vmem_limit_bytes):
        blk //= 2
    return blk


def _declared_feature_dim(prog: KernelProgram) -> Optional[int]:
    """Widest declared last-dim extent across the program's arrays
    (None when nothing is declared — callers fall back to 256)."""
    dims = [s.shape[-1] for s in prog.arrays.values()
            if s.shape and s.shape[-1] is not None]
    return max(dims) if dims else None


def _declared_dtype_bytes(prog: KernelProgram) -> int:
    """Widest declared element byte width — the conservative width for
    the VMEM budget (arrays inherit the program default, f32)."""
    from repro.analysis.opstats import dtype_byte_width
    widths = []
    for s in prog.arrays.values():
        try:
            widths.append(dtype_byte_width(s.dtype))
        except ValueError:
            pass   # unknown dtype name: budget it as f32 below
    return max(widths, default=4)


def make_tile_op(prog: KernelProgram,
                 config: Optional[SaturatorConfig] = None,
                 row_block: Optional[int] = None) -> TileOp:
    """Saturate ``prog`` and build both the Pallas op and its jnp oracle.

    The Pallas emitter is picked by ``config.emitter`` through the PR-8
    registry (:mod:`repro.core.emit`); ``None`` means ``"pallas"``."""
    cfg = config or SaturatorConfig(mode="accsat", cost_model="tpu_v5e")
    sk = saturate_program(prog, cfg)
    # emission follows the configuration that actually *built* sk: a
    # ladder-degraded build (repro.runtime.guard) carries its cheap
    # config in sk.config, and re-running the full schedule search here
    # would re-hit whatever failed
    ecfg = sk.config
    from .emit import get_emitter
    emitter = get_emitter(ecfg.emitter or "pallas")
    if emitter.info.target != "pallas":
        raise ValueError(f"make_tile_op needs a pallas emitter, got "
                         f"{emitter.info.name!r}")
    pk = None
    if sk.ladder_level != "ref":
        # reuse the pipeline's ScheduleResult when it computed one (cost
        # mode, or a cache-hit replay): the schedule depends only on the
        # choice + cost model, not the emitter, so this skips a second
        # identical search and keeps the Pallas emission aligned with
        # the cached statement order
        try:
            pgen = emitter.generator_cls(
                sk.ssa, sk.extraction, bulk=ecfg.use_bulk,
                reuse_temps=ecfg.use_cse,
                schedule=sk.kernel.schedule
                if sk.kernel.schedule is not None
                else ecfg.schedule,
                sched_cost_model=ecfg.make_schedule_cost_model(prog))
            pk = pgen.generate_pallas()
        except Exception as e:   # ladder contract: emission never fatal
            from repro.runtime.guard import classify_failure
            from .telemetry import telemetry
            telemetry().record_degradation(
                prog.name, "jax", classify_failure(e, "pallas_emit"))
            pk = None

    jax_fn = sk.kernel.fn
    in_names = sk.kernel.in_arrays
    scalar_names = sk.kernel.scalars

    def jax_ref(*arrays, **scalars):
        args = list(arrays) + [scalars[s] for s in scalar_names]
        # out arrays in the jnp path need explicit buffers
        full_args = []
        ai = iter(arrays)
        for name in in_names:
            spec = prog.arrays[name]
            if spec.role == "out":
                full_args.append(jnp.zeros_like(arrays[0]))
            else:
                full_args.append(next(ai))
        full_args += [scalars[s] for s in scalar_names]
        out = jax_fn(*full_args)
        return out[0] if len(out) == 1 else out

    if pk is None:
        # degraded op: no Pallas kernel — apply() delegates to jax_ref
        # (the saturated JAX kernel, or the reference interpreter when
        # the ladder bottomed out at "ref")
        return TileOp(name=prog.name, pk=None, jax_ref=jax_ref,
                      row_block=row_block,
                      source=sk.kernel.source, sk=sk)

    # row_block=None: sized per call from the operands that arrive
    op = TileOp(name=prog.name, pk=pk, jax_ref=jax_ref, row_block=row_block,
                source=pk.source, sk=sk)
    if cfg.verify != "off":
        # the grid pass (PR 9): statically certify the launch plan this
        # op will run — coverage, write disjointness, bounds (incl. the
        # pad region), exact VMEM fit — before anything executes
        from repro.verify import verify_tile_op
        verify_tile_op(op)
    return op
