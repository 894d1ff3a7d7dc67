#!/usr/bin/env python3
"""Bring-up check: the main path on a TPU, through the repo's own drivers.

    python chip_smoke.py               # one chip: device, serve, train
    python chip_smoke.py --four-chips  # 2x2 mesh: the sharded path only

One chip: serves minitron-4b at full published width (``launch.serve``,
prefill logits checked against the ``ref`` kernels) and trains
whisper-small at full width (``launch.train``), with every tile op,
flash attention and its backward running as compiled Pallas. Weights are
random, from a fixed seed. ``--four-chips`` checks mistral-nemo-12b
sharded over ``model=4`` against one device (depth cut to 4 layers),
then serves the full-depth model sharded.

Each phase prints one line; any failure exits non-zero, and no TPU means
exit 1 before anything runs. The last line of standard output is the
JSON result. Everything runs in this one process. Compiled programs go
to ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
# last-position logits of the Pallas path vs the reference kernels (or of
# the sharded vs the one-device run): relative L2 error. Both sides run
# the same bf16 model; they differ in where bf16 rounding happens (the
# Pallas tile ops compute in f32 and round once), which compounds over
# the layers to about 1e-2.
LOGITS_REL_TOL = 5e-2
CLEAN_LEVELS = ("hit", "warm", "cold")


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_device(expect: int):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: jax.devices()[0].platform is {dev.platform!r}")
    if len(devs) < expect:
        fail(f"need {expect} TPU chips, found {len(devs)}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not next to this script ({ROOT})")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.hardware import chip_for_device_kind
    from repro.launch.compile_cache import enable_compile_cache
    chip = chip_for_device_kind(dev.device_kind)
    cache = enable_compile_cache()
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)} spec={chip.name} compile_cache={cache}",
          flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def check_guard(phase: str):
    """Nothing on the path may have degraded or fallen back to jnp."""
    from repro.core.telemetry import telemetry
    g = telemetry().snapshot()["guard"]
    low = {k: v for k, v in g["ladder_levels"].items()
           if k not in CLEAN_LEVELS}
    if g["runtime_fallbacks"] or g["degradations"] or low:
        fail(f"{phase}: runtime_fallbacks={g['runtime_fallbacks']} "
             f"degradations={g['degradations']} ladder_levels="
             f"{g['ladder_levels']}")
    return g["ladder_levels"]


def phase_serve():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.launch.serve import Request, Server
    if ops.current_impl() != "pallas":
        fail(f"serve: kernels dispatch to {ops.current_impl()!r}, "
             "not pallas")
    t0 = time.perf_counter()
    srv = Server("minitron-4b", smoke=False, max_batch=4)
    cfg = srv.cfg
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, max_new=16, prompt=rng.integers(
        1, cfg.vocab, size=128 - i).astype(np.int32)) for i in range(4)]
    prompts = np.stack([np.pad(r.prompt, (128 - len(r.prompt), 0))
                        for r in reqs])
    out = srv.generate(reqs)
    n_tok = sum(len(v) for v in out.values())
    if n_tok != 4 * 16 or any(not 0 <= t < cfg.vocab
                              for v in out.values() for t in v):
        fail(f"serve: bad tokens {out}")
    wall = time.perf_counter() - t0

    tokens = jnp.asarray(prompts)
    logits, cache = srv.model.prefill(srv.params, tokens)
    ops.set_impl("ref")
    try:
        logits_ref, _ = srv.model.prefill(srv.params, tokens)
    finally:
        ops.set_impl(None)
    if not bool(jnp.all(jnp.isfinite(logits))):
        fail("serve: non-finite prefill logits")
    err = rel_err(logits, logits_ref)
    if not err <= LOGITS_REL_TOL:
        fail(f"serve: prefill logits rel err {err:.3e} vs ref "
             f"> {LOGITS_REL_TOL}")
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    hlo = srv._decode.lower(srv.params, cache, tok).as_text()
    kernels = sorted({k for k in ("tile_rmsnorm", "tile_swiglu",
                                  "tile_rotary") if k in hlo})
    if "tpu_custom_call" not in hlo or len(kernels) < 3:
        fail(f"serve: decode step lacks the Pallas tile kernels "
             f"(found {kernels})")
    levels = check_guard("serve")
    n_params = sum(x.size for x in jax.tree.leaves(srv.params))
    print(f"serve: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"params={n_params} requests={len(out)} tokens={n_tok} "
          f"prefill_logits_rel_err={err:.3e} (tol {LOGITS_REL_TOL}) "
          f"decode_kernels={kernels} ladder={levels} "
          f"wall_incl_setup={wall:.1f}s", flush=True)
    del srv, logits, logits_ref, cache


def phase_train():
    import jax
    from repro.launch.train import build_trainer
    steps = 3
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt:
        tr = build_trainer("whisper-small", smoke=False, steps=steps,
                           batch=8, seq=256, ckpt_dir=ckpt,
                           ckpt_every=steps + 1)
        n_params = sum(x.size for x in jax.tree.leaves(tr.params))
        out = tr.run()
    losses = out["losses"]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        fail(f"train: losses {losses}")
    levels = check_guard("train")
    print(f"train: whisper-small params={n_params} batch=8 seq=256 "
          f"steps={steps} losses={[round(x, 4) for x in losses]} "
          f"ladder={levels} wall_incl_setup="
          f"{time.perf_counter() - t0:.1f}s", flush=True)


def phase_four_chips():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.launch import steps as S
    from repro.launch.mesh import make_debug_mesh
    from repro.models import get_model
    from repro.parallel import ctx, param_specs, to_named

    mesh = make_debug_mesh(data=1, model=4)
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)

    def sharded(cfg):
        model = get_model(cfg)
        psh = to_named(param_specs(cfg, jax.eval_shape(model.init, key),
                                   mesh), mesh)
        return model, jax.jit(model.init, out_shardings=psh)(key)

    def serve(model, cfg, params, tokens, n_new, mesh_on):
        prefill = jax.jit(S.make_prefill_step(model, cfg))
        decode = jax.jit(S.make_serve_step(model))
        with ctx.activate(mesh if mesh_on else None):
            logits, cache = prefill(params, {"tokens": tokens})
            first = logits
            out = []
            for _ in range(n_new):
                tok = jnp.argmax(logits[:, -1], -1)[:, None]
                out.append(np.asarray(tok[:, 0]))
                logits, cache = decode(params, cache, tok.astype(jnp.int32))
        return first, logits, np.stack(out, 1)

    # 1) depth cut to 4: sharded prefill + one decode vs one device
    t0 = time.perf_counter()
    cfg4 = dataclasses.replace(get_config("mistral-nemo-12b"), n_layers=4)
    model, params = sharded(cfg4)
    toks = rng.integers(1, cfg4.vocab, size=(2, 128)).astype(np.int32)
    tok_sh = jax.device_put(toks, NamedSharding(mesh, P()))
    pre_s, dec_s, gen_s = serve(model, cfg4, params, tok_sh, 2, True)
    params1 = jax.device_put(params, jax.devices()[0])
    del params
    pre_1, dec_1, gen_1 = serve(model, cfg4, params1, jnp.asarray(toks), 2,
                                False)
    del params1
    e_pre, e_dec = rel_err(pre_s, pre_1), rel_err(dec_s, dec_1)
    if not (e_pre <= LOGITS_REL_TOL and e_dec <= LOGITS_REL_TOL):
        fail(f"four-chips: sharded vs one device rel err prefill "
             f"{e_pre:.3e} decode {e_dec:.3e} > {LOGITS_REL_TOL}")
    check_guard("four-chips")
    print(f"four-chips compare: mistral-nemo-12b n_layers=4 mesh=data1x"
          f"model4 prefill_rel_err={e_pre:.3e} decode_rel_err={e_dec:.3e} "
          f"(tol {LOGITS_REL_TOL}) tokens_sharded={gen_s.tolist()} "
          f"tokens_one_device={gen_1.tolist()} "
          f"wall_incl_setup={time.perf_counter() - t0:.1f}s", flush=True)

    # 2) full depth, sharded: answer 2 requests
    t0 = time.perf_counter()
    cfg = get_config("mistral-nemo-12b")
    model, params = sharded(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    toks = rng.integers(1, cfg.vocab, size=(2, 64)).astype(np.int32)
    pre, last, gen = serve(model, cfg, params,
                           jax.device_put(toks, NamedSharding(mesh, P())),
                           8, True)
    if not (bool(jnp.all(jnp.isfinite(pre))) and
            bool(jnp.all(jnp.isfinite(last)))):
        fail("four-chips: non-finite logits at full depth")
    if not ((0 <= gen) & (gen < cfg.vocab)).all():
        fail(f"four-chips: bad tokens {gen}")
    check_guard("four-chips")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:4])
    print(f"four-chips serve: mistral-nemo-12b layers={cfg.n_layers} "
          f"params={n_params} requests=2 tokens={gen.size} "
          f"tokens={gen.tolist()} peak_bytes_per_chip={peak} "
          f"wall_incl_setup={time.perf_counter() - t0:.1f}s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 2x2 mesh")
    args = ap.parse_args(argv)
    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_serve()
        phase_train()
    from repro.launch.compile_cache import compile_cache_counts
    print(f"compile cache: {compile_cache_counts()}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
