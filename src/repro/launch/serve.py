"""Serving driver: batched greedy prefill + decode.

A minimal server loop:
  * requests arrive with prompts of different lengths;
  * ``generate`` takes them in fixed batches of up to ``max_batch``,
    left-pads each batch's prompts to its longest, runs one prefill, then
    advances the whole batch one token per tick via the jitted decode
    step (the same function the decode dry-run cells lower) until the
    batch's longest ``max_new`` is reached. There is no continuous
    batching: a finished sequence keeps its slot until its batch ends.

CPU-scale entry:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke
Full published width (on a chip):
  PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b --full
"""
from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import SaturatorConfig
from repro.core.telemetry import telemetry
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model

from repro.cache import default_cache_dir

# Default persistent saturation-cache location for the serving CLI: the
# decode hot path pays beam-search cost once per kernel shape across
# boots, not once per process (disable with --no-cache). User-private
# ($XDG_CACHE_HOME/repro/sat_cache) — cached entries are replayed into
# generated code, so the directory must not be writable by other users.
DEFAULT_CACHE_DIR = str(default_cache_dir())


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,)
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Server:
    def __init__(self, arch: str, *, smoke: bool = True, max_batch: int = 4,
                 max_seq: int = 128, seed: int = 0,
                 cache_dir: Optional[str] = None,
                 verify: Optional[str] = None):
        # every saturated tile op the model layers dispatch through
        # repro.kernels.ops is built (or replayed) via this cache
        if cache_dir is not None:
            ops.set_saturation_cache(cache_dir)
        if verify is not None:
            ops.set_saturation_verify(verify)
        arch = ARCH_IDS.get(arch, arch)
        self.cfg = get_smoke_config(arch) if smoke else get_config(arch)
        self.model = get_model(self.cfg)
        # jitted: the random init fuses into bf16 outputs (no f32 copy of
        # the largest weight alive at full width)
        self.params = jax.jit(self.model.init)(jax.random.PRNGKey(seed))
        self.max_batch = max_batch
        self.max_seq = max_seq
        self._decode = jax.jit(self.model.decode_step)
        # metrics are mutated from every serving thread — concurrent
        # generate() calls are supported, so counter updates take this
        # lock (prevents lost increments / torn read-modify-write)
        self._metrics_lock = threading.Lock()
        self.metrics = {"prefills": 0, "decode_ticks": 0, "tokens": 0}

    def _bump(self, key: str, n: int = 1):
        with self._metrics_lock:
            self.metrics[key] += n

    def _prefill_batch(self, prompts: np.ndarray):
        tokens = jnp.asarray(prompts, jnp.int32)
        if self.cfg.family == "encdec":
            frames = jnp.zeros((tokens.shape[0], tokens.shape[1],
                                self.cfg.d_model), jnp.float32)
            logits, cache = self.model.prefill(self.params, tokens, frames)
        else:
            logits, cache = self.model.prefill(self.params, tokens)
        self._bump("prefills")
        return logits, cache

    def generate(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Serve a list of requests greedily, ``max_batch`` at a time."""
        pending = list(requests)
        results: Dict[int, List[int]] = {}
        while pending:
            batch = pending[:self.max_batch]
            pending = pending[self.max_batch:]
            plen = max(len(r.prompt) for r in batch)
            prompts = np.stack([
                np.pad(r.prompt, (plen - len(r.prompt), 0)) for r in batch])
            logits, cache = self._prefill_batch(prompts)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            steps = max(r.max_new for r in batch)
            for t in range(steps - 1):
                for i, r in enumerate(batch):
                    if len(r.out) < r.max_new:
                        r.out.append(int(tok[i, 0]))
                logits, cache = self._decode(self.params, cache, tok)
                tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
                self._bump("decode_ticks")
                self._bump("tokens", len(batch))
            for i, r in enumerate(batch):
                if len(r.out) < r.max_new:
                    r.out.append(int(tok[i, 0]))
                r.done = True
                results[r.rid] = r.out
        snap = telemetry().snapshot()
        with self._metrics_lock:
            # snapshot() is already internally consistent; the lock only
            # orders the dict swap against concurrent counter bumps.
            # snap["guard"] carries the PR-10 robustness counters
            # (ladder levels, degradations, breaker events, chaos fires).
            self.metrics["saturation"] = snap
        return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the published config instead of the smoke one")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--cache-dir", default=DEFAULT_CACHE_DIR,
                    help="persistent saturation cache directory")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the on-disk saturation cache")
    ap.add_argument("--verify", default=None,
                    choices=["off", "cheap", "full"],
                    help="static verification level for every kernel "
                         "build (default: REPRO_VERIFY, else off)")
    args = ap.parse_args(argv)

    # one documented front door for the cache/verify side-channels:
    # explicit arg > CLI flag > env var (REPRO_SAT_CACHE / REPRO_VERIFY)
    sat = SaturatorConfig.from_env(flags=args)
    enable_compile_cache()
    srv = Server(args.arch, smoke=args.smoke,
                 cache_dir=sat.cache_dir or None, verify=sat.verify)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, srv.cfg.vocab,
                                        size=args.prompt_len
                                        - (i % 3)).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    out = srv.generate(reqs)
    dt = time.time() - t0
    sat = srv.metrics.get("saturation", {})
    print(f"arch={args.arch} served {len(out)} requests, "
          f"{srv.metrics['tokens']} tokens in {dt:.1f}s "
          f"({srv.metrics['prefills']} prefills, "
          f"{srv.metrics['decode_ticks']} ticks)")
    print(f"  saturation cache: hits={sat.get('cache_hits', 0)} "
          f"warm={sat.get('cache_warm_starts', 0)} "
          f"misses={sat.get('cache_misses', 0)} "
          f"hit_rate={sat.get('cache_hit_rate', 0.0):.2f}")
    guard = sat.get("guard", {})
    print(f"  guard: levels={guard.get('ladder_levels', {})} "
          f"degradations={sum(guard.get('degradations', {}).values())} "
          f"breaker={guard.get('breaker_events', {})} "
          f"runtime_fallbacks="
          f"{sum(guard.get('runtime_fallbacks', {}).values())}")
    for rid in sorted(out):
        print(f"  req{rid}: {out[rid]}")
    return out


if __name__ == "__main__":
    main()
