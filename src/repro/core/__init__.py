# The paper's primary contribution: equality saturation for directive-style
# parallel code, adapted to JAX/TPU (see DESIGN.md).
from repro.analysis import (LatencyModel, OpStats, RooflineCostModel,
                            node_stats)
from .cost import CostModel, TPUCostModel, count_flops, count_ops, instruction_mix
from .dsl import (ArrayHandle, Expr, KernelProgram, c, call, exp, fma,
                  gelu_tanh, log, maximum, minimum, recip, rmax, rmean,
                  rothalf, rsqrt, rsum, select, sigmoid, silu, softplus,
                  sqrt, square, tanh, toint, v)
from .beam import BeamStats, beam_search
from .egraph import EGraph, P, Pattern, PatVar, V, add_expr
from .extract import (ExtractionResult, extract_dag, extract_exact,
                      optimality_gap)
from .emit import EMITTER_NAMES, Emitter, EmitterInfo, get_emitter
from .ir import ENode
from .jaxpr_bridge import BridgeUnsupported, maybe_saturate, saturate_jax_fn
from .pallasgen import (PallasGenerator,  # deprecated-ok (re-export)
                        SyncPallasGenerator, TileOp, make_tile_op,
                        pick_row_block)
from .pipeline import (CACHE_ENV_VAR, MODES, VERIFY_ENV_VAR, CacheConfig,
                       SaturatedKernel, SaturatorConfig, ScheduleConfig,
                       SearchConfig, VerifyConfig, saturate_all_modes,
                       saturate_program)
from .reference import run_reference
from .rules import (EXTENDED_RULES, PAPER_RULES, TPU_RULES, Rule, run_rules)
from .schedule import (SCHEDULE_MODES, ScheduleResult, compute_schedule,
                       is_legal_order, random_topological_order)
from .ssa import SSAResult, build_ssa
from .telemetry import SaturationTelemetry, reset_telemetry, telemetry

__all__ = [
    "CACHE_ENV_VAR", "SaturationTelemetry", "reset_telemetry", "telemetry",
    "LatencyModel", "OpStats", "RooflineCostModel", "node_stats",
    "CostModel", "TPUCostModel", "count_flops", "count_ops",
    "instruction_mix", "ArrayHandle", "Expr", "KernelProgram", "EGraph",
    "ENode", "ExtractionResult", "extract_dag", "extract_exact",
    "BeamStats", "beam_search", "optimality_gap",
    "BridgeUnsupported", "maybe_saturate", "saturate_jax_fn",
    "EMITTER_NAMES", "Emitter", "EmitterInfo", "get_emitter",
    "PallasGenerator", "SyncPallasGenerator",
    "TileOp", "make_tile_op", "pick_row_block", "MODES", "VERIFY_ENV_VAR",
    "SearchConfig", "ScheduleConfig", "CacheConfig", "VerifyConfig",
    "SaturatedKernel", "SaturatorConfig", "saturate_all_modes",
    "saturate_program", "run_reference", "PAPER_RULES", "EXTENDED_RULES",
    "TPU_RULES", "Rule", "run_rules", "build_ssa", "SSAResult",
    "add_expr", "P", "V", "Pattern", "PatVar", "toint",
    "SCHEDULE_MODES", "ScheduleResult", "compute_schedule",
    "is_legal_order", "random_topological_order",
]
