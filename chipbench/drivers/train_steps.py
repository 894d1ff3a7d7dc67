"""Training through the trainer ``launch.train.build_trainer`` returns.

The trainer's own step (``build_step``) and state are driven step after
step, each step's batch from its ``ShardedTokenPipeline`` (made from the
seed) and each loss read to the host, as ``ElasticTrainer.run`` does.
``run`` itself is not called: it stops only at ``total_steps`` and
checkpoints on the way and at its end, and the window must be a fixed
time with no checkpoint in it. ``total_steps`` only shapes the learning
rate schedule (warm-up of one step, cosine decay over ``schedule_steps``,
then a floor of a tenth).

Set-up builds the trainer and drives its first ``check_steps`` steps:
they compile the step and are the steps the reference follows. The
window then continues the same trainer, from the next step, for
``--seconds``; it runs from the start of its first step to the moment
its last loss is on the host.

Correctness compares, against the plain reference: each checked step's
loss; per leaf, the norm of the first step's clipped gradient as the
optimizer holds it (its first moment over ``1 - b1``); per leaf, the norm
of the parameters' change over the checked steps.

``ctx.controls`` (calibration only) names what to read beside the
program, each put in the program's place against the same reference:
``fp8``, the reference computed in fp8; ``fault_half_batch``, the
reference trained on half of each batch (the mean over the rest);
``fault_token``, the reference trained on targets off by one position
(every token altered); ``fault_state_unchanged``, a step that returns
its state unchanged (the first loss again, no moment, no change).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import bench
from chipbench.reference import encdec


def leaf_gap(prog: List[float], ref: List[float],
             keep: np.ndarray) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    prog, ref = np.asarray(prog), np.asarray(ref)
    scale = np.maximum(ref, np.median(ref[keep]))
    return float(np.max((np.abs(prog - ref) / scale)[keep]))


def run(ctx: bench.RunContext) -> bench.DriverResult:
    from repro.launch.train import build_trainer

    tr, model = ctx.traffic, ctx.config["model"]
    B, S, n_check = tr["batch"], tr["seq"], tr["check_steps"]
    tracer = bench.Tracer(ctx.trace, ctx.trace_dir, tr["trace_lead_s"],
                          tr["trace_span_s"])
    trainer = build_trainer(
        ctx.config["arch"], smoke=ctx.smoke, steps=tr["schedule_steps"],
        batch=B, seq=S, lr=tr["lr"], seed=ctx.seed,
        ckpt_dir=str(bench.CACHE_DIR / "ckpt"), ckpt_every=10 ** 9,
        cache_dir=ctx.sat_cache_dir)
    step_fn, pipe = trainer.build_step(trainer.num_shards)
    stats = {"batch": B, "seq": S, "enc_seq": S}

    def step(i: int):
        with tracer.span("chipbench.batch_prep"):
            batch = pipe.batch_at(i)
        with tracer.span("chipbench.step", **stats):
            trainer.params, trainer.opt_state, loss = step_fn(
                trainer.params, trainer.opt_state, batch)
        with tracer.span("chipbench.loss_sync"):
            float(loss)

    # set-up: the first steps compile the step and are the reference's
    losses, batches, first_grad, change = checked_steps(
        trainer, step_fn, pipe, n_check, tr["opt"]["b1"])
    bench.check_guard("set-up steps")
    ctx.sat_build_s = bench.sat_build_s()
    compiles = bench.CompileCounter()
    ctx.mark_setup_done()

    i = n_check
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    tracer.start_window(t0)
    n_steps = 0
    while time.perf_counter() < t_end:
        tracer.poll(may_start=True)
        step(i)
        i += 1
        n_steps += 1
    t_last = time.perf_counter()
    tracer.stop()
    ctx.notes.update(steps_in_window=n_steps, window_s=t_last - t0,
                     compiles_in_window=compiles.compiles,
                     compile_cache_hits_in_window=compiles.cache_hits)
    bench.check_guard("window")
    memory_peak = bench.memory_peak()
    e2e = {"train_tok_s": n_steps * B * S / (t_last - t0)}

    trainer.params = trainer.opt_state = None
    del trainer, step_fn
    gc.collect()
    checks, controls = compare(ctx, model, batches, losses, first_grad,
                               change)
    return bench.DriverResult(e2e, n_steps + n_check, 0, checks, memory_peak,
                              controls)


def checked_steps(trainer, step_fn, pipe, n: int, b1: float):
    """Drive the trainer's first ``n`` steps and read what the reference
    is compared on: the losses, the batches, per leaf the norm of the
    first step's clipped gradient (the first moment over ``1 - b1``) and
    of the parameters' change over the ``n`` steps."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        return [float(jnp.linalg.norm(x.astype(jnp.float32)))
                for x in jax.tree.leaves(tree)]

    p0 = trainer.params
    losses, batches, first_grad = [], [], None
    for i in range(n):
        batch = pipe.batch_at(i)
        trainer.params, trainer.opt_state, loss = step_fn(
            trainer.params, trainer.opt_state, batch)
        losses.append(float(loss))
        batches.append(batch)
        if i == 0:
            first_grad = [g / (1 - b1)
                          for g in norms(trainer.opt_state["m"])]
    change = norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        trainer.params, p0))
    return losses, batches, first_grad, change


def frames_for(model: Dict, batch: int, seq: int):
    """The encoder's input frames the configuration feeds each step: one
    fixed normal draw (key 0), as long as the token sequence."""
    import jax
    import jax.numpy as jnp
    return jax.random.normal(jax.random.PRNGKey(0),
                             (batch, seq, model["d_model"]), jnp.float32)


def reference(ctx, model, batches, precision="f32") -> Dict:
    tr = ctx.traffic
    opt = dict(tr["opt"], lr=tr["lr"], total_steps=tr["schedule_steps"],
               warmup_steps=max(tr["schedule_steps"] // 10, 1))
    return encdec.train_steps(ctx.seed, model, opt, batches,
                              frames_for(model, tr["batch"], tr["seq"]),
                              precision)


def readings(ref: Dict, losses, first_grad, change) -> Dict[str, float]:
    """The compared numbers of one run against one reference."""
    grad_ref = np.asarray(ref["grad"])
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone: they are left out of the change
    moving = grad_ref >= 1e-3 * np.median(grad_ref)
    everything = np.ones_like(moving)
    return {
        "loss_rel_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, ref["losses"])),
        "grad_norm_gap": leaf_gap(first_grad, ref["grad"], everything),
        "change_norm_gap": leaf_gap(change, ref["change"], moving),
    }


def control_run(ctx, model, batches, ref, name: str) -> Dict:
    """What the control ``name`` gives in the program's place."""
    if name == "fp8":
        return reference(ctx, model, batches, "fp8")
    if name == "fault_half_batch":
        half = ctx.traffic["batch"] // 2
        half_ctx = dataclasses.replace(
            ctx, traffic=dict(ctx.traffic, batch=half))
        return reference(half_ctx, model,
                         [{k: v[:half] for k, v in b.items()}
                          for b in batches])
    if name == "fault_token":
        return reference(ctx, model, [dict(b, labels=b["tokens"])
                                      for b in batches])
    if name == "fault_state_unchanged":
        n = len(ref["grad"])
        return {"losses": [ref["losses"][0]] * len(batches),
                "grad": [0.0] * n, "change": [0.0] * n}
    raise ValueError(f"unknown control {name!r}")


def compare(ctx, model, batches, losses, first_grad, change):
    """Checks of the numbers the cell's limits name, for the program and
    for each control; the others are printed beside them."""
    ref = reference(ctx, model, batches)
    got = readings(ref, losses, first_grad, change)
    ctx.notes.update(program_losses=losses, reference_losses=ref["losses"],
                     **{k: v for k, v in got.items() if k not in ctx.limits})

    def checks(values):
        return [bench.Check(name, values[name], limit)
                for name, limit in ctx.limits.items()]
    controls = {}
    for name in ctx.controls:
        r = control_run(ctx, model, batches, ref, name)
        values = readings(ref, r["losses"], r["grad"], r["change"])
        ctx.notes.update({f"{name}.{k}": v for k, v in values.items()
                          if k not in ctx.limits})
        controls[name] = checks(values)
    return checks(got), controls
