"""End-to-end training (loss decreases, elastic recovery, determinism) and
serving (continuous batching) on smoke configs."""
import numpy as np
import jax
import pytest

from repro.launch.train import build_trainer
from repro.launch.serve import Request, Server


@pytest.mark.slow
def test_train_loss_decreases(tmp_path):
    tr = build_trainer("minitron-4b", smoke=True, steps=20, batch=8,
                       seq=64, ckpt_dir=str(tmp_path), lr=1e-3)
    out = tr.run()
    losses = out["losses"]
    assert len(losses) == 20
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


@pytest.mark.slow
def test_train_recovers_from_failure(tmp_path):
    tr = build_trainer("granite-8b", smoke=True, steps=16, batch=4,
                       seq=32, ckpt_dir=str(tmp_path),
                       inject={9: ("node_loss", 1)})
    out = tr.run()
    assert out["recoveries"] == 1
    assert out["final_step"] == 16
    assert out["elastic_events"][0]["kind"] == "node_loss"
    assert np.isfinite(out["losses"]).all()


@pytest.mark.slow
def test_train_failure_replay_matches_clean_run(tmp_path):
    """Deterministic data replay: a run interrupted+recovered converges to
    the same losses as an uninterrupted run (same seeds, same steps)."""
    t1 = build_trainer("qwen2-vl-2b", smoke=True, steps=12, batch=4,
                       seq=32, ckpt_dir=str(tmp_path / "a"), seed=5)
    clean = t1.run()["losses"]
    t2 = build_trainer("qwen2-vl-2b", smoke=True, steps=12, batch=4,
                       seq=32, ckpt_dir=str(tmp_path / "b"), seed=5,
                       inject={7: ("node_loss", 1)})
    recovered = t2.run()["losses"]
    # after recovery the replayed steps recompute identical losses
    np.testing.assert_allclose(clean, recovered, rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_train_with_compression(tmp_path):
    tr = build_trainer("minitron-4b", smoke=True, steps=10, batch=4,
                       seq=32, ckpt_dir=str(tmp_path), compress="int8_ef",
                       lr=1e-3)
    out = tr.run()
    assert np.isfinite(out["losses"]).all()
    assert out["losses"][-1] < out["losses"][0] * 1.2


@pytest.mark.slow
def test_serve_continuous_batching():
    srv = Server("mamba2-1.3b", smoke=True, max_batch=3)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
        1, srv.cfg.vocab, size=8 + i).astype(np.int32), max_new=4)
        for i in range(5)]
    out = srv.generate(reqs)
    assert set(out) == set(range(5))
    assert all(len(v) == 4 for v in out.values())
    assert srv.metrics["prefills"] == 2  # 3 + 2 under max_batch=3


def test_serve_greedy_deterministic():
    srv = Server("minitron-4b", smoke=True, max_batch=2)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, srv.cfg.vocab, size=8).astype(np.int32)
    r1 = srv.generate([Request(0, prompt.copy(), 5)])
    r2 = srv.generate([Request(0, prompt.copy(), 5)])
    assert r1[0] == r2[0]


def test_serve_counts_host_syncs():
    srv = Server("minitron-4b", smoke=True, max_batch=2)
    rng = np.random.default_rng(2)
    reqs = [Request(i, rng.integers(1, srv.cfg.vocab, size=8)
                    .astype(np.int32), n) for i, n in enumerate((3, 1, 2))]
    out = srv.generate(reqs)
    # one device-to-host read per token served
    assert srv.metrics["host_syncs"] == sum(map(len, out.values())) == 6
    assert srv.metrics["decode_ticks"] == 2 + 1


def _first_decode(arch):
    srv = Server(arch, smoke=True, max_batch=2)
    prompts = np.random.default_rng(3).integers(
        1, srv.cfg.vocab, (2, 8)).astype(np.int32)
    logits, cache = srv._prefill_batch(prompts)
    tok = jax.numpy.argmax(logits[:, -1], -1)[:, None].astype(np.int32)
    return srv, cache, tok


@pytest.mark.parametrize("arch", ["minitron-4b", "zamba2-2.7b"])
def test_serve_decode_consumes_its_cache(arch):
    """The decode step is given the cache to write in place: the buffers
    it was given are gone, and the compiled step's outputs share all of
    the cache's bytes with its inputs."""
    srv, cache, tok = _first_decode(arch)
    given = jax.tree.leaves(cache)
    _, cache = srv._decode(srv.params, cache, tok)
    assert all(a.is_deleted() for a in given)
    assert not any(a.is_deleted() for a in jax.tree.leaves(cache))
    (shape, entry), = srv.metrics["decode_cache"].items()
    assert shape == "2x264"
    assert entry["cache_bytes"] == sum(a.nbytes for a in given)
    assert entry["aliased_bytes"] == entry["cache_bytes"]


def test_serve_decode_compiles_once_per_shape():
    """Recording the compiled step's memory does not compile it twice:
    the jitted call reuses the executable, and a new batch size adds one
    compile and one entry."""
    srv, cache, tok = _first_decode("minitron-4b")
    compiles = []

    def on_time(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_time)
    try:
        for _ in range(3):
            _, cache = srv._decode(srv.params, cache, tok)
        assert len(compiles) == 1
        logits, cache = srv._prefill_batch(np.ones((1, 8), np.int32))
        tok = jax.numpy.argmax(logits[:, -1], -1)[:, None].astype(np.int32)
        n = len(compiles)
        for _ in range(2):
            _, cache = srv._decode(srv.params, cache, tok)
        assert len(compiles) == n + 1
    finally:
        jax.monitoring.unregister_event_duration_listener(on_time)
    assert sorted(srv.metrics["decode_cache"]) == ["1x264", "2x264"]
