"""Closed-loop serving through ``launch.serve.Server.generate``.

``clients`` clients each wait for their answer before sending again; the
server is built with ``max_batch = clients``, so every call of
``generate`` serves one full batch and no request queues. Prompts are
``prompt_len`` ids drawn uniformly from the vocabulary by the seed; each
asks for ``max_new`` greedy tokens.

``generate`` returns only when its batch is done, so the harness times
tokens with thin wrappers around the instance's decode and prefill
calls. ``generate`` reads every token to the host (``int``) before it
calls decode again, so a decode call's entry time is when the previous
token reached the host; the last token arrives when ``generate``
returns.

Correctness: after the window, ``check_requests`` finished requests
drawn from the seed, taken round the batch's slots so that every slot is
in the sample, are run through the plain reference (prompt and served
tokens), and the widest gap by which a served token's reference logit
lies below the reference's best is compared with the cell's limit.

``ctx.controls`` (calibration only) names controls to read beside the
program: ``fp8`` puts in the served tokens' place those that the
reference computed in fp8 puts first at each position of the same rows.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from chipbench import bench
from chipbench.reference import dense_lm


class Timeline:
    """Entry times of the decode calls of the current batch, and the
    harness's host spans around them."""

    def __init__(self, srv, tracer: bench.Tracer, batch: int,
                 prompt_len: int):
        self.decode_entries: List[float] = []
        self.batch, self.prompt_len = batch, prompt_len
        decode, prefill = srv._decode, srv._prefill_batch

        def timed_decode(params, cache, tok):
            self.decode_entries.append(time.perf_counter())
            tracer.poll()
            pos = self.prompt_len + len(self.decode_entries) - 1
            with tracer.span("chipbench.decode", batch=self.batch, pos=pos):
                return decode(params, cache, tok)

        def timed_prefill(prompts):
            tracer.poll()
            with tracer.span("chipbench.prefill", batch=prompts.shape[0],
                             seq=prompts.shape[1]):
                return prefill(prompts)

        srv._decode = timed_decode
        srv._prefill_batch = timed_prefill

    def reset(self):
        self.decode_entries = []


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx: bench.RunContext) -> bench.DriverResult:
    from repro.launch.serve import Request, Server

    tr, model = ctx.traffic, ctx.config["model"]
    clients, plen, max_new = tr["clients"], tr["prompt_len"], tr["max_new"]
    tracer = bench.Tracer(ctx.trace, ctx.trace_dir, tr["trace_lead_s"],
                          tr["trace_span_s"])
    srv = Server(ctx.config["arch"], smoke=ctx.smoke, max_batch=clients,
                 seed=ctx.seed, cache_dir=ctx.sat_cache_dir)
    timeline = Timeline(srv, tracer, clients, plen)
    rng = np.random.default_rng(ctx.seed)
    next_rid = [0]

    def make_batch(n_new: int):
        reqs = []
        for _ in range(clients):
            prompt = rng.integers(0, model["vocab"], size=plen,
                                  dtype=np.int64).astype(np.int32)
            reqs.append(Request(rid=next_rid[0], prompt=prompt,
                                max_new=n_new))
            next_rid[0] += 1
        return reqs

    # warm-up: the cell's own shapes only: one prefill and two decode
    # steps (the second takes the cache a decode step made)
    srv.generate(make_batch(min(3, max_new)))
    bench.check_guard("warm-up")
    ctx.sat_build_s = bench.sat_build_s()
    compiles = bench.CompileCounter()
    ctx.mark_setup_done()

    batches = []
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    tracer.start_window(t0)
    while time.perf_counter() < t_end:
        tracer.poll(may_start=True)
        with tracer.span("chipbench.batch_prep"):
            reqs = make_batch(max_new)
        timeline.reset()
        t_submit = time.perf_counter()
        with tracer.span("chipbench.submit"):
            out = srv.generate(reqs)
        t_done = time.perf_counter()
        batches.append((t_submit, timeline.decode_entries + [t_done],
                        reqs, out))
    tracer.stop()
    ctx.notes.update(compiles_in_window=compiles.compiles,
                     compile_cache_hits_in_window=compiles.cache_hits)
    bench.check_guard("window")
    memory_peak = bench.memory_peak()

    ttft, gaps, tokens, attempted, failed = [], [], 0, 0, 0
    for t_submit, times, reqs, out in batches:
        # every request of a batch gets its k-th token at the same time
        in_window = sum(t <= t_end for t in times)
        tokens += in_window * len(reqs)
        ttft += [(times[0] - t_submit) * 1e3] * len(reqs)
        steps = [(b - a) * 1e3 for a, b in zip(times, times[1:]) if b <= t_end]
        gaps += steps * len(reqs)
        attempted += len(reqs)
        failed += sum(len(out.get(r.rid, [])) != r.max_new for r in reqs)
    e2e = {"ttft_p90_ms": _percentile(ttft, 90),
           "itl_p95_ms": _percentile(gaps, 95) if gaps else float("nan"),
           "serve_tok_s": tokens / ctx.seconds}
    ctx.notes.update(batches=len(batches), requests=attempted,
                     gaps=len(gaps), tokens_in_window=tokens)

    # the program's state goes before the reference runs
    sample = _sample(ctx, batches, tr["check_requests"])
    ctx.notes.update(checked_requests=len(sample["rows"]),
                     checked_tokens=int(sample["served"].size))
    srv.params = None
    del srv, batches, timeline
    gc.collect()
    start = plen - 1
    ref = dense_lm.logits_at(ctx.seed, model, sample["rows"], start)
    limit = ctx.limits["served_logit_gap"]
    checks = [bench.Check("served_logit_gap",
                          widest_gap(ref, sample["served"]), limit)]
    controls = {}
    for name in ctx.controls:
        if name != "fp8":
            raise ValueError(f"unknown control {name!r}")
        low = dense_lm.logits_at(ctx.seed, model, sample["rows"], start, "fp8")
        controls[name] = [bench.Check("served_logit_gap",
                                      widest_gap(ref, low.argmax(-1)), limit)]
    return bench.DriverResult(e2e, attempted, failed, checks, memory_peak,
                              controls)


def _sample(ctx, batches, n: int) -> Dict[str, np.ndarray]:
    """``n`` finished requests drawn from the seed, as (prompt + served)
    token rows and the served tokens. The picks go round the batch's
    slots, each from a batch drawn from the seed, so a sample of at least
    ``clients`` requests holds every slot."""
    by_slot: Dict[int, list] = {}
    for _, _, reqs, out in batches:
        for slot, r in enumerate(reqs):
            if len(out.get(r.rid, [])) == r.max_new:
                by_slot.setdefault(slot, []).append((r, out[r.rid]))
    rng = np.random.default_rng([ctx.seed, 1])
    queues = {s: [done[i] for i in rng.permutation(len(done))]
              for s, done in sorted(by_slot.items())}
    picks = []
    while len(picks) < n and any(queues.values()):
        for s in queues:
            if queues[s] and len(picks) < n:
                picks.append(queues[s].pop())
    rows, served = [], []
    for r, toks in picks:
        toks = np.asarray(toks, np.int32)
        rows.append(np.concatenate([r.prompt, toks[:-1]]))
        served.append(toks)
    return {"rows": np.stack(rows), "served": np.stack(served)}


def widest_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """Widest gap, over the sample, between the reference's best logit
    and the reference logit of the token that was served."""
    return float(dense_lm.served_gaps(ref_logits, served).max())
