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
Tensor-parallel over the four chips of one host:
  PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b \
      --full --model-parallel 4
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
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import SaturatorConfig
from repro.core.telemetry import telemetry
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import get_model
from repro.parallel import cache_specs, ctx, param_specs, to_named

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
    """Greedy batched server. With a mesh (given, or the one active in
    ``parallel.ctx`` when the server is built) the weights are made
    already sharded by ``param_specs``, prefill and decode trace and run
    under that mesh, and the KV cache is laid out by ``cache_specs``.
    Without one, everything stays on the default device."""

    def __init__(self, arch: str, *, smoke: bool = True, max_batch: int = 4,
                 max_seq: int = 128, seed: int = 0,
                 cache_dir: Optional[str] = None,
                 verify: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        # every saturated tile op the model layers dispatch through
        # repro.kernels.ops is built (or replayed) via this cache
        if cache_dir is not None:
            ops.set_saturation_cache(cache_dir)
        if verify is not None:
            ops.set_saturation_verify(verify)
        arch = ARCH_IDS.get(arch, arch)
        self.cfg = get_smoke_config(arch) if smoke else get_config(arch)
        self.model = get_model(self.cfg)
        self.mesh = mesh if mesh is not None else ctx.active_mesh()
        key = jax.random.PRNGKey(seed)
        if self.mesh is None:
            # jitted: the random init fuses into bf16 outputs (no f32
            # copy of the largest weight alive at full width)
            self.params = jax.jit(self.model.init)(key)
            step = self.model.decode_step
        else:
            # made in place, each chip its own shard: the whole model
            # need not fit one chip
            shapes = jax.eval_shape(self.model.init, key)
            psh = to_named(param_specs(self.cfg, shapes, self.mesh),
                           self.mesh)
            self.params = jax.jit(self.model.init, out_shardings=psh)(key)
            step = self._decode_sharded
        # the cache is donated: each tick writes its new K/V rows into
        # the buffers it is given, and the caller's cache is consumed
        self._decode_jit = jax.jit(step, donate_argnums=(1,))
        self.max_batch = max_batch
        self.max_seq = max_seq
        # metrics are mutated from every serving thread — concurrent
        # generate() calls are supported, so counter updates take this
        # lock (prevents lost increments / torn read-modify-write)
        self._metrics_lock = threading.Lock()
        self.metrics = {"prefills": 0, "decode_ticks": 0, "tokens": 0,
                        "host_syncs": 0, "decode_cache": {},
                        "mesh": (dict(self.mesh.shape) if self.mesh
                                 is not None else None)}

    def _pin_cache(self, cache):
        """The cache as ``cache_specs`` lays it out on the mesh."""
        return jax.lax.with_sharding_constraint(
            cache, to_named(cache_specs(self.cfg, cache, self.mesh),
                            self.mesh))

    def _decode_sharded(self, params, cache, tok):
        logits, cache = self.model.decode_step(params, self._pin_cache(cache),
                                               tok)
        return logits, self._pin_cache(cache)

    def _decode(self, params, cache, tok):
        """One decode tick; consumes ``cache``. The first tick of each
        cache shape also records what the compiled step keeps in place
        (``_record_decode_cache``; two threads meeting a new shape at
        once both record it, with equal entries)."""
        with ctx.activate(self.mesh):
            if _cache_key(cache) not in self.metrics["decode_cache"]:
                self._record_decode_cache(params, cache, tok)
            return self._decode_jit(params, cache, tok)

    def _record_decode_cache(self, params, cache, tok):
        """``metrics["decode_cache"][shape]``: the cache's bytes on one
        device, as laid out there, and of the compiled step's, the bytes
        its outputs share with its donated inputs (``aliased_bytes``, all
        of the cache when it is updated in place) and its temporaries
        (``temp_bytes``, near the cache's size where a whole copy of it is
        written). The jit's call that follows reuses this compile."""
        mem = self._decode_jit.lower(params, cache, tok).compile() \
            .memory_analysis()
        entry = {"cache_bytes": sum(
                     a.addressable_shards[0].data.on_device_size_in_bytes()
                     for a in jax.tree.leaves(cache)),
                 "aliased_bytes": int(mem.alias_size_in_bytes),
                 "temp_bytes": int(mem.temp_size_in_bytes)}
        with self._metrics_lock:
            self.metrics["decode_cache"][_cache_key(cache)] = entry

    def _bump(self, key: str, n: int = 1):
        with self._metrics_lock:
            self.metrics[key] += n

    def _read_tokens(self, batch: List[Request], tok):
        """Append this tick's token to every unfinished request: one
        device-to-host read each."""
        unfinished = [i for i, r in enumerate(batch)
                      if len(r.out) < r.max_new]
        with TraceAnnotation("repro.serve.readback", syncs=len(unfinished)):
            for i in unfinished:
                batch[i].out.append(int(tok[i, 0]))
        self._bump("host_syncs", len(unfinished))

    def _prefill_batch(self, prompts: np.ndarray):
        tokens = jnp.asarray(prompts, jnp.int32)
        if self.mesh is not None:
            tokens = jax.device_put(tokens, NamedSharding(self.mesh, P()))
        with ctx.activate(self.mesh):
            if self.cfg.family == "encdec":
                frames = jnp.zeros((tokens.shape[0], tokens.shape[1],
                                    self.cfg.d_model), jnp.float32)
                logits, cache = self.model.prefill(self.params, tokens,
                                                   frames)
            else:
                logits, cache = self.model.prefill(self.params, tokens)
            if self.mesh is not None:
                cache = self._pin_cache(cache)
        self._bump("prefills")
        return logits, cache

    def generate(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Serve a list of requests greedily, ``max_batch`` at a time.

        Host spans, recorded only while a profiler runs, mark each
        batch's prefill (``repro.serve.prefill``) and each readback of
        tokens to the host (``repro.serve.readback``)."""
        pending = list(requests)
        results: Dict[int, List[int]] = {}
        while pending:
            batch = pending[:self.max_batch]
            pending = pending[self.max_batch:]
            plen = max(len(r.prompt) for r in batch)
            prompts = np.stack([
                np.pad(r.prompt, (plen - len(r.prompt), 0)) for r in batch])
            with TraceAnnotation("repro.serve.prefill", batch=len(batch),
                                 seq=plen):
                logits, cache = self._prefill_batch(prompts)
                tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            steps = max(r.max_new for r in batch)
            for t in range(steps - 1):
                self._read_tokens(batch, tok)
                logits, cache = self._decode(self.params, cache, tok)
                tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
                self._bump("decode_ticks")
                self._bump("tokens", len(batch))
            self._read_tokens(batch, tok)
            for r in batch:
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


def _cache_key(cache) -> str:
    """A decode cache's shape: batch, then positions where it keeps K/V."""
    if "k" in cache:
        return "x".join(map(str, cache["k"].shape[1:4:2]))
    return str(jax.tree.leaves(cache["ssm"])[0].shape[1])


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
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel over this many devices "
                         "(a data=1, model=N mesh); 1 serves on one")
    ap.add_argument("--verify", default=None,
                    choices=["off", "cheap", "full"],
                    help="static verification level for every kernel "
                         "build (default: REPRO_VERIFY, else off)")
    args = ap.parse_args(argv)

    # one documented front door for the cache/verify side-channels:
    # explicit arg > CLI flag > env var (REPRO_SAT_CACHE / REPRO_VERIFY)
    sat = SaturatorConfig.from_env(flags=args)
    enable_compile_cache()
    mesh = (make_local_mesh(1, args.model_parallel)
            if args.model_parallel > 1 else None)
    srv = Server(args.arch, smoke=args.smoke,
                 cache_dir=sat.cache_dir or None, verify=sat.verify,
                 mesh=mesh)
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
          f"{srv.metrics['decode_ticks']} ticks, "
          f"{srv.metrics['host_syncs']} host syncs, "
          f"mesh {srv.metrics['mesh']})")
    for shape, c in srv.metrics["decode_cache"].items():
        print(f"  decode cache {shape}: {c['cache_bytes']} bytes, "
              f"aliased {c['aliased_bytes']}, temp {c['temp_bytes']}")
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
