"""Each plain reference against the program at its smoke sizes on the
CPU: same weights from the seed, same inputs, results within what bf16
rounding allows."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench
from chipbench.reference import dense_lm
from helpers_bench import FIXTURES

SEED = 2 ** 31 + 77


def test_dense_lm_matches_program_logits():
    from repro.configs import get_smoke_config
    from repro.models import get_model
    model = get_model(get_smoke_config("minitron_4b"))
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    tokens = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(
        np.int32)
    got = np.asarray(model.logits(params, jnp.asarray(tokens)))
    cfg = bench.load_json(FIXTURES / "minitron-4b-smoke.json")["model"]
    ref = dense_lm.logits_at(SEED, cfg, tokens, 0)
    # bf16 weights and activations over two layers: about 1 % of the
    # logits' range; a wrong weight or layer is off by the whole range
    assert np.abs(got - ref).max() < 0.02 * np.abs(ref).max()
    # only the positions from ``start`` on are returned
    tail = dense_lm.logits_at(SEED, cfg, tokens, 30)
    np.testing.assert_allclose(tail, ref[:, 30:], rtol=1e-5, atol=1e-5)


def test_encdec_training_matches_program_steps():
    from repro.launch.train import build_trainer
    drv = bench.driver("train_steps")
    B, S = 4, 32
    trainer = build_trainer("whisper-small", smoke=True, steps=10, batch=B,
                            seq=S, seed=SEED, ckpt_dir="unused",
                            ckpt_every=10 ** 9)
    step_fn, pipe = trainer.build_step(1)
    losses, batches, grad, change = drv.checked_steps(trainer, step_fn, pipe,
                                                      3, 0.9)
    cfg = bench.load_json(FIXTURES / "whisper-small-smoke.json")
    import helpers_bench
    ctx, _ = helpers_bench.smoke_context("whisper-small.train", seed=SEED)
    ref = drv.reference(ctx, cfg["model"], batches)
    got = drv.readings(ref, losses, grad, change)
    assert got["loss_rel_gap"] < 1e-3
    assert got["grad_norm_gap"] < 2e-2
    assert got["change_norm_gap"] < 2e-2
