"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip, plants one fault in the
program at its smoke sizes on the CPU, drives the rest of a run and
reads ``correct``: a decode step that returns its cache unchanged, a
token altered where it is produced, a training step that returns its
state unchanged, one that leaves out half of the batch (the mean over
the rest), and one whose targets are off by one position.
"""
import jax.numpy as jnp
import pytest

import helpers_bench


def test_sound_smoke_runs_are_correct():
    assert helpers_bench.smoke_run("minitron-4b.decode")["correct"]
    assert helpers_bench.smoke_run("whisper-small.train")["correct"]


def _decode_cache_unchanged(orig):
    def decode_step(self, params, cache, token):
        logits, _ = orig(self, params, cache, token)
        return logits, cache
    return decode_step


def _decode_token_altered(orig):
    def decode_step(self, params, cache, token):
        logits, cache = orig(self, params, cache, token)
        return jnp.roll(logits, 1, axis=-1), cache
    return decode_step


@pytest.mark.parametrize("fault", [_decode_cache_unchanged,
                                   _decode_token_altered])
def test_serve_fault_is_caught(monkeypatch, fault):
    from repro.models.lm import LM
    monkeypatch.setattr(LM, "decode_step", fault(LM.decode_step))
    line = helpers_bench.smoke_run("minitron-4b.decode")
    assert line["correct"] is False
    check = line["checks"]["served_logit_gap"]
    assert check["value"] > check["limit"]


def _train_state_unchanged(monkeypatch):
    import repro.launch.train as train
    monkeypatch.setattr(train, "apply_updates",
                        lambda params, grads, state, cfg: (params, state))


def _train_half_batch(monkeypatch):
    from repro.models.whisper import EncDecLM
    orig = EncDecLM.loss

    def loss(self, params, batch):
        half = batch["tokens"].shape[0] // 2
        return orig(self, params, {k: v[:half] for k, v in batch.items()})
    monkeypatch.setattr(EncDecLM, "loss", loss)


def _train_token_altered(monkeypatch):
    from repro.models.whisper import EncDecLM
    orig = EncDecLM.loss

    def loss(self, params, batch):
        # targets off by one position: every token altered
        return orig(self, params, dict(batch, labels=batch["tokens"]))
    monkeypatch.setattr(EncDecLM, "loss", loss)


@pytest.mark.parametrize("plant", [_train_state_unchanged, _train_half_batch,
                                   _train_token_altered])
def test_train_fault_is_caught(monkeypatch, plant):
    plant(monkeypatch)
    line = helpers_bench.smoke_run("whisper-small.train")
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
