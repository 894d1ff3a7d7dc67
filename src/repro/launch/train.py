"""Training driver: elastic fault-tolerant loop over any assigned arch.

CPU-scale entry (smoke/examples):
  PYTHONPATH=src python -m repro.launch.train --arch minitron-4b --smoke \\
      --steps 50 --batch 8 --seq 128
Full published width (on a chip):
  PYTHONPATH=src python -m repro.launch.train --arch whisper-small --full \\
      --steps 4 --batch 8 --seq 256

Checkpointing is async+atomic, failures are recovered elastically, and
the gradient exchange can be compressed (--compress bf16|int8_ef).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import default_cache_dir
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import SaturatorConfig
from repro.core.telemetry import telemetry
from repro.data import DataConfig, ShardedTokenPipeline
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.optim import OptConfig, apply_updates, init_opt_state
from repro.parallel.compression import Compressor
from repro.runtime import ElasticTrainer, FailureInjector, TrainLoopConfig


def build_trainer(arch: str, *, smoke: bool, steps: int, batch: int,
                  seq: int, ckpt_dir: str, compress: str = "none",
                  inject: Optional[dict] = None, lr: float = 3e-4,
                  num_shards: int = 1, seed: int = 0,
                  cache_dir: Optional[str] = None,
                  verify: Optional[str] = None,
                  ckpt_every: Optional[int] = None) -> ElasticTrainer:
    # persist saturation results (norm/optimizer tile ops) across runs:
    # a restarted or elastically-recovered job replays committed kernels
    # instead of re-searching
    if cache_dir is not None:
        ops.set_saturation_cache(cache_dir)
    if verify is not None:
        ops.set_saturation_verify(verify)
    arch = ARCH_IDS.get(arch, arch)
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = get_model(cfg)
    opt_cfg = OptConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                        total_steps=steps)
    comp = Compressor(compress)

    rng = jax.random.PRNGKey(seed)
    params = jax.jit(model.init)(rng)
    opt_state = init_opt_state(params, opt_cfg)
    comp_state = comp.init_state(params) if compress == "int8_ef" else None

    def build_step(n_shards: int):
        pipe = ShardedTokenPipeline(DataConfig(
            vocab=cfg.vocab, seq_len=seq, global_batch=batch,
            seed=seed, shard_id=0, num_shards=1))

        @jax.jit
        def step(params, opt_state, batch_np):
            b = {k: jnp.asarray(v) for k, v in batch_np.items()}
            if cfg.family == "encdec":
                b["frames"] = jax.random.normal(
                    jax.random.PRNGKey(0),
                    (b["tokens"].shape[0], b["tokens"].shape[1],
                     cfg.d_model), jnp.float32)
            loss, grads = jax.value_and_grad(model.loss)(params, b)
            # DP gradient exchange with optional compression (on one
            # process this is the identity wire format; wire-byte savings
            # are accounted in the roofline)
            if compress != "none":
                g_c, _ = comp.compress(grads, comp_state)
                grads = comp.decompress(g_c)
                grads = jax.tree.map(lambda g, p: g.astype(jnp.float32),
                                     grads, params)
            new_p, new_s = apply_updates(params, grads, opt_state, opt_cfg)
            return new_p, new_s, loss

        def step_np(params, opt_state, batch_np):
            return step(params, opt_state, batch_np)

        return step_np, pipe

    loop_cfg = TrainLoopConfig(total_steps=steps,
                               ckpt_every=ckpt_every or max(steps // 4, 1),
                               ckpt_dir=ckpt_dir)
    return ElasticTrainer(loop_cfg, build_step, params, opt_state,
                          num_shards=num_shards,
                          injector=FailureInjector(inject))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8", "int8_ef"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--cache-dir", default=str(default_cache_dir()),
                    help="persistent saturation cache directory "
                         "(user-private by default)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the on-disk saturation cache")
    ap.add_argument("--verify", default=None,
                    choices=["off", "cheap", "full"],
                    help="static verification level for every kernel "
                         "build (default: REPRO_VERIFY, else off)")
    args = ap.parse_args(argv)

    inject = {args.inject_failure_at: ("node_loss", 1)} \
        if args.inject_failure_at else None
    # one documented front door for the cache/verify side-channels:
    # explicit arg > CLI flag > env var (REPRO_SAT_CACHE / REPRO_VERIFY)
    sat = SaturatorConfig.from_env(flags=args)
    enable_compile_cache()
    trainer = build_trainer(args.arch, smoke=args.smoke, steps=args.steps,
                            batch=args.batch, seq=args.seq,
                            ckpt_dir=args.ckpt_dir, lr=args.lr,
                            compress=args.compress, inject=inject,
                            cache_dir=sat.cache_dir or None,
                            verify=sat.verify)
    t0 = time.time()
    out = trainer.run()
    losses = out["losses"]
    print(f"arch={args.arch} steps={out['final_step']} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"recoveries={out['recoveries']} wall={time.time()-t0:.1f}s")
    sat = telemetry().snapshot()
    print(f"  saturation cache: hits={sat['cache_hits']} "
          f"warm={sat['cache_warm_starts']} misses={sat['cache_misses']} "
          f"bridge_fallbacks={sum(sat['bridge_fallbacks'].values())}")
    ver = sat["verify"]
    print(f"  verify: runs={ver['runs']} errors={ver['errors']} "
          f"rules_checked={ver['rules_checked']} "
          f"schedules_certified={ver['schedules_certified']} "
          f"findings_by_pass={ver['findings_by_pass']}")
    guard = sat["guard"]
    print(f"  guard: levels={guard['ladder_levels']} "
          f"degradations={sum(guard['degradations'].values())} "
          f"breaker={guard['breaker_events']} "
          f"runtime_fallbacks={sum(guard['runtime_fallbacks'].values())} "
          f"recoveries={guard['elastic_recoveries']}")
    assert losses[-1] < losses[0], "training did not reduce loss"
    return out


if __name__ == "__main__":
    main()
