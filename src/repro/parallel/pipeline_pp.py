"""Pipeline parallelism (GPipe-style) over ``shard_map`` +
``collective_permute``.

For meshes deeper than DP×TP (e.g. 1000+ nodes where a 123B model wants
PP=8), the layer stack is split into S stages along a ``stage`` mesh axis;
microbatches stream through stages with ``ppermute`` hand-offs. The
schedule below is the classic GPipe fill-drain loop expressed as one
``lax.fori_loop`` inside ``shard_map`` — every stage executes the same
program (SPMD), idle ticks are masked, so it lowers cleanly at any mesh
size.

Bubble fraction = (S-1)/(M+S-1) for M microbatches; compute/comm overlap
comes from XLA scheduling the ppermute of microbatch i+1 against the
stage compute of microbatch i (async collective-permute).

Used by tests/test_pipeline_pp.py (equivalence vs single-device stack)
and selectable in launch/train.py via ``--pp``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable, n_stages: int,
                   n_micro: int, x, stage_params, *, axis: str = "stage"):
    """Run ``stage_fn(params_s, micro_x) -> micro_y`` as a GPipe pipeline.

    x: (n_micro, micro_batch, ...) input microbatches (all on stage 0);
    stage_params: pytree with leading stage axis, sharded over ``axis``.
    Returns (n_micro, micro_batch, ...) outputs (from the last stage,
    gathered to all).
    """
    assert x.shape[0] == n_micro

    def per_stage(params_local, x_local):
        # params_local: this stage's params (leading axis 1) ; x_local: full
        # microbatch stream (only stage 0's copy is meaningful)
        params_s = jax.tree.map(lambda a: a[0], params_local)
        stage = lax.axis_index(axis)
        S = n_stages
        M = n_micro
        T = M + S - 1                      # fill-drain ticks
        micro_shape = x_local.shape[1:]

        def tick(t, carry):
            buf, outs = carry
            # stage s works on microbatch (t - s) when 0 <= t-s < M
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 ingests a fresh microbatch; others use the handed-off
            inp = jnp.where(
                stage == 0,
                x_local[jnp.clip(mb_idx, 0, M - 1)],
                buf)
            out = stage_fn(params_s, inp)
            out = jnp.where(active, out, buf)
            # hand off to the next stage
            nxt = lax.ppermute(out, axis,
                               [(i, (i + 1) % S) for i in range(S)])
            # last stage records its finished microbatch
            done_idx = t - (S - 1)
            is_done = (stage == S - 1) & (done_idx >= 0) & (done_idx < M)
            outs = lax.cond(
                is_done,
                lambda o: lax.dynamic_update_slice(
                    o, out[None].astype(o.dtype),
                    (jnp.clip(done_idx, 0, M - 1),) + (0,) * len(micro_shape)),
                lambda o: o, outs)
            return (nxt, outs)

        buf0 = jnp.zeros(micro_shape, x_local.dtype)
        outs0 = jnp.zeros((M,) + micro_shape, x_local.dtype)
        _, outs = lax.fori_loop(0, T, tick, (buf0, outs0))
        # broadcast final outputs from the last stage to all stages
        outs = lax.all_gather(outs, axis)[S - 1]
        return outs

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),          # params sharded by stage; x replicated
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def split_layers_to_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major."""
    def re(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages}"
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree.map(re, stacked_params)


def make_stage_fn(layer_fn: Callable):
    """Wrap a single-layer fn into a stage fn scanning its layer slice."""
    def stage_fn(stage_params, x):
        def body(h, lp):
            return layer_fn(lp, h), None
        h, _ = lax.scan(body, x, stage_params)
        return h
    return stage_fn
