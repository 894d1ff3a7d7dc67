"""The serve check's sample: drawn from the seed, round the batch's
slots, so that a sample of ``clients`` requests holds every slot."""
import dataclasses
import types

import numpy as np

from chipbench import bench


@dataclasses.dataclass
class _Req:
    rid: int
    prompt: np.ndarray
    max_new: int


def _batches(n_batches, clients, max_new=3, unfinished=()):
    out = []
    for b in range(n_batches):
        reqs = [_Req(b * clients + s, np.full(4, b * clients + s, np.int32),
                     max_new) for s in range(clients)]
        served = {r.rid: [r.rid] * (max_new - (r.rid in unfinished))
                  for r in reqs}
        out.append((0.0, [], reqs, served))
    return out


def _slots(sample, clients):
    return sorted({int(row[0]) % clients for row in sample["rows"]})


def test_sample_holds_every_slot_and_follows_the_seed():
    drv = bench.driver("serve_closed_loop")
    ctx = types.SimpleNamespace(seed=2 ** 31 + 5)
    batches = _batches(9, 16)
    sample = drv._sample(ctx, batches, 16)
    assert _slots(sample, 16) == list(range(16))
    # prompt + all served tokens but the last, and the served tokens
    assert sample["rows"].shape == (16, 4 + 2)
    assert sample["served"].shape == (16, 3)
    again = drv._sample(ctx, batches, 16)
    np.testing.assert_array_equal(sample["rows"], again["rows"])
    other = drv._sample(types.SimpleNamespace(seed=7), batches, 16)
    assert not np.array_equal(sample["rows"], other["rows"])


def test_sample_goes_round_the_slots_and_skips_unfinished_requests():
    drv = bench.driver("serve_closed_loop")
    ctx = types.SimpleNamespace(seed=11)
    sample = drv._sample(ctx, _batches(3, 4, unfinished={0, 4, 8}), 8)
    rids = [int(row[0]) for row in sample["rows"]]
    assert {0, 4, 8}.isdisjoint(rids)          # slot 0 never finished
    assert _slots(sample, 4) == [1, 2, 3]
    assert len(rids) == len(set(rids)) == 8
