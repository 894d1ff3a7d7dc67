"""PR-8 emitter registry and the Pallas emitter.

Covers the registry front door (``repro.core.emit``), the deprecated
class aliases, the agreement of the two emitters one extraction feeds
(the Pallas kernel and its jnp twin) across every tile kernel, and the
acceptance sweep (Pallas sources verify clean under both rule sets).
"""
import numpy as np
import pytest

import jax

from repro.core import (SaturatorConfig, ScheduleConfig, make_tile_op,
                        saturate_program)
from repro.core.emit import (EMITTER_NAMES, Emitter, emitter_cache_id,
                             get_emitter)
from repro.kernels.tile_programs import PROGRAMS, get_tile_op
from repro.verify import verify_pallas_kernel

TILE_NAMES = tuple(sorted(PROGRAMS))


# -- registry ---------------------------------------------------------------
def test_registry_names_and_targets():
    assert EMITTER_NAMES == ("jax", "pallas")
    targets = {}
    for name in EMITTER_NAMES:
        em = get_emitter(name)
        assert isinstance(em, Emitter)
        assert em.info.name == name
        assert em.info.version >= 1
        targets[name] = em.info.target
    assert targets == {"jax": "jax", "pallas": "pallas"}


def test_unknown_emitter_rejected():
    with pytest.raises(ValueError, match="unknown emitter"):
        get_emitter("cuda")
    with pytest.raises(ValueError, match="unknown emitter"):
        emitter_cache_id("cuda")
    with pytest.raises(ValueError, match="emitter"):
        SaturatorConfig(schedule_cfg=ScheduleConfig(emitter="cuda"))


def test_default_emitters_contribute_no_cache_key():
    """Pre-registry configs must keep byte-identical fingerprints: the
    default emitters map to None."""
    assert emitter_cache_id(None) is None
    assert emitter_cache_id("jax") is None
    assert emitter_cache_id("pallas") is None


def test_registry_emit_matches_direct_generator():
    sk = saturate_program(PROGRAMS["rmsnorm"](),
                          SaturatorConfig(mode="accsat",
                                          cost_model="tpu_v5e",
                                          tpu_rules=True))
    from repro.core.codegen import JaxCodeGenerator
    direct = JaxCodeGenerator(sk.ssa, sk.extraction, bulk=True).generate()
    via_registry = get_emitter("jax").emit(sk.ssa, sk.extraction, bulk=True)
    assert via_registry.source == direct.source
    from repro.core.pallasgen import SyncPallasGenerator
    pdirect = SyncPallasGenerator(sk.ssa, sk.extraction,
                                  bulk=True).generate_pallas()
    pvia = get_emitter("pallas").emit(sk.ssa, sk.extraction, bulk=True)
    assert pvia.source == pdirect.source


def test_deprecated_aliases_warn_and_match():
    """The pre-PR-8 class names still work (they are the documented
    migration path) but raise DeprecationWarning on construction."""
    sk = saturate_program(PROGRAMS["swiglu"](),
                          SaturatorConfig(mode="accsat",
                                          cost_model="tpu_v5e",
                                          tpu_rules=True))
    from repro.core.codegen import (CodeGenerator,      # deprecated-ok
                                    JaxCodeGenerator)
    from repro.core.pallasgen import (PallasGenerator,  # deprecated-ok
                                      SyncPallasGenerator)
    with pytest.warns(DeprecationWarning, match="CodeGenerator"):
        old = CodeGenerator(sk.ssa, sk.extraction,         # deprecated-ok
                            bulk=True).generate()
    new = JaxCodeGenerator(sk.ssa, sk.extraction, bulk=True).generate()
    assert old.source == new.source
    with pytest.warns(DeprecationWarning, match="PallasGenerator"):
        pold = PallasGenerator(sk.ssa, sk.extraction,      # deprecated-ok
                               bulk=True).generate_pallas()
    pnew = SyncPallasGenerator(sk.ssa, sk.extraction,
                               bulk=True).generate_pallas()
    assert pold.source == pnew.source


def _tile_inputs(prog, seed=0):
    # mirrors benchmarks.measure.tile_inputs_for, which cannot be
    # imported here: benchmarks entry points re-exec on import to pin
    # PYTHONHASHSEED, which would replace the pytest process
    from repro.analysis import TILE_SHAPE
    rng = np.random.default_rng(seed)
    arrays = []
    for spec in prog.arrays.values():
        if spec.role not in ("in", "inout"):
            continue
        shape = getattr(spec, "shape", None) or TILE_SHAPE
        shape = tuple(TILE_SHAPE[i] if d is None else int(d)
                      for i, d in enumerate(shape))
        arrays.append(rng.uniform(0.1, 1.0, size=shape).astype(np.float32))
    return arrays, {s: 0.5 for s in prog.scalars}


# -- the two emitters of one extraction agree -------------------------------
@pytest.mark.parametrize("name", TILE_NAMES)
def test_emitters_agree_on_every_tile_program(name):
    """One ``get_tile_op`` build, run through its Pallas kernel (interpret
    mode) and through its jnp twin, gives the same outputs. ``atol``
    covers outputs that cancel to near zero (layernorm, rotary), where
    one rounding step is a large relative error; it is below one f32
    ulp at the O(1) magnitude of these outputs."""
    op = get_tile_op(name)
    assert op.pk is not None and op.pk.emitter == "pallas"
    arrays, scalars = _tile_inputs(op.sk.ssa.prog)
    args = [jax.numpy.asarray(a) for a in arrays]
    got = op.apply(*args, interpret=True, **scalars)
    want = op.jax_ref(*args, **scalars)
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want) == len(op.pk.out_arrays)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-7, err_msg=name)


# -- acceptance sweep: both rule sets ---------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("tpu_rules", [False, True])
def test_sync_sources_verify_clean_both_rule_sets(tpu_rules):
    """Acceptance: Pallas emitter sources pass cheap verification with
    zero errors across all 13 tile kernels under both the Table-I rule
    set and the +TPU strength-reduction set."""
    for name in TILE_NAMES:
        cfg = SaturatorConfig(
            mode="accsat", cost_model="tpu_v5e", tpu_rules=tpu_rules,
            schedule_cfg=ScheduleConfig(schedule="cost"))
        op = make_tile_op(PROGRAMS[name](), cfg)
        rep = verify_pallas_kernel(op.pk, op.sk.ssa)
        assert not rep.errors(), (name, tpu_rules,
                                  [f"[{f.code}] {f.message}"
                                   for f in rep.errors()])
