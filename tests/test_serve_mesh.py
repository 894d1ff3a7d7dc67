"""``launch.serve.Server`` under a mesh against ``Server`` on one device
and against the plain float32 reference, at mistral-nemo-12b's smoke
sizes (head_dim x heads != d_model, GQA 4/2) on four virtual CPU
devices in a child process, so this session keeps its single device.

Under ``(1, 2)`` the cache's two KV heads split one per device, as the 8
do over 4 chips at full size; under ``(1, 4)`` they do not divide and
the cache splits its sequence axis instead (``parallel.cache_specs``).
The ``(1, 2)`` run is made again with the Pallas tile ops (interpret
mode), which run through ``shard_map`` under a mesh.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SEED = 2 ** 31 + 101

_CHILD = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import Request, Server
from repro.parallel import ctx

seed = int(sys.argv[1])
prompts = np.random.default_rng(seed).integers(0, 512, (2, 24)).astype(np.int32)
out = {"prompts": prompts.tolist()}
toks = None
for shape in (None, (1, 2), (1, 4)):
    mesh = None if shape is None else make_local_mesh(*shape)
    srv = Server("mistral-nemo-12b", smoke=True, max_batch=2, seed=seed,
                 mesh=mesh)
    logits, cache = srv._prefill_batch(prompts)
    steps = [logits[:, -1]]
    if toks is None:
        # the one-device run's greedy tokens are fed to every run, so a
        # near-tie that rounds the other way does not change the inputs
        toks = [jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)]
    for t in range(2):
        logits, cache = srv._decode(srv.params, cache, toks[t])
        steps.append(logits[:, -1])
        if len(toks) < 3:
            toks.append(jnp.argmax(logits[:, -1], -1)[:, None]
                        .astype(jnp.int32))
    collectives = []
    if mesh is not None:
        # the compiled step's collectives (the jit's own executable)
        with ctx.activate(mesh):
            hlo = srv._decode_jit.lower(srv.params, cache,
                                        toks[0]).compile().as_text()
        for line in hlo.splitlines():
            op = re.search(r"= (.*?) (all-gather|all-reduce|all-to-all|"
                           r"collective-permute|reduce-scatter)(-start)?\(",
                           line)
            if op:
                collectives.append(max(
                    int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\[([\d,]*)\]", op.group(1))))
    reqs = [Request(rid=i, prompt=prompts[i], max_new=3) for i in range(2)]
    served = srv.generate(reqs)
    out[str(shape)] = {
        "logits": np.stack([np.asarray(s, np.float32) for s in steps], 1).tolist(),
        "tokens": np.concatenate([np.asarray(t) for t in toks], 1).tolist(),
        "served": [served[i] for i in range(2)],
        "cache_shape": list(cache["k"].shape),
        "cache_shard": list(cache["k"].sharding.shard_shape(cache["k"].shape)),
        "wg_shard": list(srv.params["layers"]["mlp"]["wg"].sharding
                         .shard_shape(srv.params["layers"]["mlp"]["wg"].shape)),
        "mesh": srv.metrics["mesh"],
        "decode_cache": srv.metrics["decode_cache"],
        "collectives": collectives}

# the Pallas tile ops (interpret mode here) under the (1, 2) mesh: each
# runs through shard_map over gathered operands, and those gathers carry
# the tile_gather scope
from repro.kernels import ops
ops.set_impl("pallas")
srv = Server("mistral-nemo-12b", smoke=True, max_batch=2, seed=seed,
             mesh=make_local_mesh(1, 2))
logits, cache = srv._prefill_batch(prompts)
steps = [logits[:, -1]]
for t in range(2):
    if t == 0:
        with ctx.activate(srv.mesh):
            hlo = jax.jit(srv._decode_sharded).lower(
                srv.params, cache, toks[0]).compile().as_text()
    logits, cache = srv._decode(srv.params, cache, toks[t])
    steps.append(logits[:, -1])
gathers = [l for l in hlo.splitlines() if re.search(r" all-gather(-start)?\(", l)]
out["pallas (1, 2)"] = {
    "logits": np.stack([np.asarray(s, np.float32) for s in steps], 1).tolist(),
    "gathers": len(gathers),
    "tile_gathers": sum("/tile_gather/" in l for l in gathers)}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _CHILD, str(SEED)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _reference(served, n_layers=None):
    """Reference logits at the prompt's last position and at the two
    decoded positions, the program's own tokens fed back."""
    sys.path.insert(0, ROOT)
    from chipbench import bench
    from chipbench.reference import dense_lm
    cfg = bench.load_json(os.path.join(
        ROOT, "tests", "bench", "fixtures",
        "mistral-nemo-12b-smoke.json"))["model"]
    if n_layers is not None:
        cfg = dict(cfg, n_layers=n_layers)
    prompts = np.asarray(served["prompts"], np.int32)
    toks = np.asarray(served["None"]["tokens"], np.int32)
    rows = np.concatenate([prompts, toks[:, :2]], 1)
    return dense_lm.logits_at(SEED, cfg, rows, prompts.shape[1] - 1)


# bf16 weights and activations: the sharded and one-device runs differ
# only in where bf16 rounding falls (the partial sums of the row-parallel
# products), about 1e-2 of the logits' norm over two layers
SHARDED_REL_TOL = 3e-2
# against the float32 reference: the same bf16 rounding, once; a model
# one layer short lies about ten times further off (asserted below)
REFERENCE_REL_TOL = 3e-2


@pytest.mark.parametrize("shape", ["(1, 2)", "(1, 4)"])
def test_sharded_server_matches_one_device(served, shape):
    one, sh = served["None"], served[shape]
    n = int(shape[-2])
    assert sh["mesh"] == {"data": 1, "model": n}
    # the MLP's columns split over the model axis (L, D, F / n)
    assert sh["wg_shard"] == one["wg_shard"][:2] + [one["wg_shard"][2] // n]
    assert _rel(sh["logits"], one["logits"]) < SHARDED_REL_TOL
    assert [len(t) for t in sh["served"]] == [3, 3]


def test_pallas_tile_ops_under_mesh(served):
    assert _rel(served["pallas (1, 2)"]["logits"],
                served["None"]["logits"]) < SHARDED_REL_TOL
    # rotary's q and swiglu's two column-parallel operands are gathered
    # under the scope; the attention output's gather is not a tile op's
    assert served["pallas (1, 2)"]["tile_gathers"] >= 3
    assert served["pallas (1, 2)"]["tile_gathers"] < \
        served["pallas (1, 2)"]["gathers"]


def test_cache_split_by_head_or_sequence(served):
    L, B, KH, S, hd = served["None"]["cache_shape"]
    assert served["None"]["cache_shard"] == [L, B, KH, S, hd]
    # 2 KV heads over 2 devices: one head each; over 4 they cannot split,
    # and the sequence axis does instead
    assert served["(1, 2)"]["cache_shard"] == [L, B, KH // 2, S, hd]
    assert served["(1, 4)"]["cache_shard"] == [L, B, KH, S // 4, hd]


@pytest.mark.parametrize("shape", ["None", "(1, 2)", "(1, 4)"])
def test_decode_keeps_its_cache_in_place(served, shape):
    """Split by head or by sequence, the decode step writes its row into
    the donated cache: the compiled step shares all of the cache's bytes
    on a device with its inputs, and no collective moves as much as one
    layer's share of the cache."""
    (key, entry), = served[shape]["decode_cache"].items()
    L, B, KH, S, hd = served[shape]["cache_shard"]
    assert key == "%dx%d" % (B, served[shape]["cache_shape"][3])
    # K and V in bf16 and the int32 position, per device
    assert entry["cache_bytes"] == 2 * 2 * L * B * KH * S * hd + 4
    assert entry["aliased_bytes"] == entry["cache_bytes"]
    if shape != "None":
        assert served[shape]["collectives"]
        assert max(served[shape]["collectives"]) < B * KH * S * hd


@pytest.mark.parametrize("shape", ["None", "(1, 2)", "(1, 4)",
                                   "pallas (1, 2)"])
def test_server_matches_plain_reference(served, shape):
    ref = _reference(served)
    assert _rel(served[shape]["logits"], ref) < REFERENCE_REL_TOL


def test_reference_tolerance_catches_a_missing_layer(served):
    assert _rel(_reference(served, n_layers=1), _reference(served)) > \
        10 * REFERENCE_REL_TOL
