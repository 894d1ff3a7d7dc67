"""Guarded saturation runtime (PR 10): budgets, degradation ladder,
circuit breaker, deterministic chaos harness, cache-fault hardening,
straggler policy, and elastic-recovery state preservation."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.keys import cache_key_for
from repro.cache.store import SaturationCache, make_entry
from repro.core import CacheConfig, SaturatorConfig, make_tile_op
from repro.core.pipeline import saturate_program
from repro.core.telemetry import telemetry
from repro.kernels.tile_programs import PROGRAMS, get_tile_op
from repro.runtime import chaos
from repro.runtime.ft import (ElasticTrainer, FailureEvent, FailureInjector,
                              StragglerPolicy, TrainLoopConfig)
from repro.runtime.guard import (BudgetExceeded, CircuitBreaker, GuardConfig,
                                 SaturationGuard, breaker_for,
                                 breakers_snapshot, classify_failure,
                                 guard_tick, reset_breakers, run_ladder)


def _base_cfg(**kw):
    return SaturatorConfig(mode="accsat", cost_model="tpu_v5e",
                           tpu_rules=True,
                           cache_cfg=CacheConfig(cache_dir=False), **kw)


@pytest.fixture(autouse=True)
def _clean_guard_state():
    telemetry().reset()
    reset_breakers()
    chaos.clear_plan()
    yield
    chaos.clear_plan()
    reset_breakers()


# -- chaos harness ---------------------------------------------------------------
def test_fault_plan_rejects_unknown_sites():
    with pytest.raises(ValueError, match="unknown fault site"):
        chaos.FaultPlan(sites=("not_a_site",))


def test_plan_from_env_parsing():
    p = chaos.plan_from_env(
        "rule_raise,exec_fail:seed=3:max_fires=inf:p=0.25:kernels=a|b")
    assert p.sites == ("rule_raise", "exec_fail")
    assert p.seed == 3
    assert p.max_fires is None
    assert p.probability == 0.25
    assert p.kernels == ("a", "b")
    assert chaos.plan_from_env("verify_error:max_fires=2").max_fires == 2
    with pytest.raises(ValueError):
        chaos.plan_from_env("nope_site")
    with pytest.raises(ValueError):
        chaos.plan_from_env("rule_raise:bogus=1")


def test_chaos_fire_pattern_is_seed_deterministic():
    plan = chaos.FaultPlan(sites=("rule_raise",), seed=5, max_fires=None,
                           probability=0.5)

    def pattern():
        with chaos.plan_scope(plan):
            return [chaos.chaos_point("rule_raise") for _ in range(64)]

    p1, p2 = pattern(), pattern()
    assert p1 == p2
    assert 5 < sum(p1) < 60   # actually probabilistic, not all/none
    # the published contract: occurrence n fires iff u01(seed, site, n) < p
    assert p1 == [chaos._u01(5, "rule_raise", i) < 0.5 for i in range(64)]


def test_chaos_max_fires_and_kernel_filter():
    with chaos.plan_scope(chaos.FaultPlan(sites=("rule_raise",),
                                          max_fires=1)):
        assert chaos.chaos_point("rule_raise")
        assert not chaos.chaos_point("rule_raise")   # budget spent
    plan = chaos.FaultPlan(sites=("rule_raise",), kernels=("rmsnorm",),
                           max_fires=None)
    with chaos.plan_scope(plan):
        assert not chaos.chaos_point("rule_raise", kernel="adamw")
        assert not chaos.chaos_point("rule_raise")   # no kernel context
        with chaos.kernel_scope("rmsnorm"):
            assert chaos.chaos_point("rule_raise")
    assert telemetry().snapshot()["guard"]["chaos_fires"]["rule_raise"] == 2


def test_chaos_inactive_is_noop():
    assert not chaos.chaos_point("rule_raise")
    chaos.maybe_raise("exec_fail")          # must not raise
    chaos.maybe_raise_os("cache_read_io", 5, "x")


# -- guard ceilings ---------------------------------------------------------------
def test_guard_tick_noop_without_active_guard():
    guard_tick("saturation", n=10**9)   # no ambient guard: free pass


def test_guard_eval_budget_trips():
    g = SaturationGuard("k", GuardConfig(eval_budget=10))
    for _ in range(10):
        g.tick("saturation")
    with pytest.raises(BudgetExceeded) as ei:
        g.tick("saturation")
    assert ei.value.trigger == "eval_budget"


def test_guard_node_class_ceilings():
    g = SaturationGuard("k", GuardConfig(node_ceiling=100,
                                         class_ceiling=50))
    g.tick("egraph", nodes=100, classes=50)   # at the ceiling: fine
    with pytest.raises(BudgetExceeded) as ei:
        g.tick("egraph", nodes=101)
    assert ei.value.trigger == "node_ceiling"
    with pytest.raises(BudgetExceeded) as ei:
        g.tick("egraph", classes=51)
    assert ei.value.trigger == "class_ceiling"


def test_guard_deadline_sampled():
    g = SaturationGuard("k", GuardConfig(deadline_s=0.0))
    with g.activate():
        with pytest.raises(BudgetExceeded) as ei:
            for _ in range(1024):   # deadline checked every 1024 ticks
                guard_tick("beam")
    assert ei.value.trigger == "deadline"


def test_classify_failure_labels():
    assert classify_failure(BudgetExceeded("deadline"), "s") \
        == "budget:deadline"
    assert classify_failure(chaos.InjectedFault("exec_fail"), "s") \
        == "chaos:exec_fail"
    os_err = OSError(28, "boom")
    os_err.chaos_site = "cache_write_io"
    assert classify_failure(os_err, "s") == "chaos:cache_write_io"
    assert classify_failure(ValueError("x"), "extract") \
        == "extract:ValueError"


# -- circuit breaker --------------------------------------------------------------
def test_breaker_state_machine():
    br = CircuitBreaker("k", threshold=2, cooldown=2)
    assert br.admit() is None and br.state == "closed"
    br.record_failure(fallback_level="ref")
    assert br.state == "closed"              # below threshold
    br.record_failure()
    assert br.state == "open"
    assert br.admit() == "ref"               # cooling down: skip
    assert br.admit() is None                # half-open: the one trial
    assert br.state == "half_open"
    br.record_failure()                      # trial failed: re-open
    assert br.state == "open"
    assert br.admit() == "ref"
    assert br.admit() is None
    br.record_success()                      # trial passed: close
    assert br.state == "closed" and br.failures == 0
    ev = telemetry().snapshot()["guard"]["breaker_events"]
    assert ev["open"] == 2 and ev["half_open"] == 2 and ev["close"] == 1


def test_breaker_registry():
    a = breaker_for(("apply", "x"), threshold=5)
    assert breaker_for(("apply", "x"), threshold=9) is a
    assert a.threshold == 5                  # first caller's policy wins
    snap = breakers_snapshot()
    assert snap["total"] == 1 and snap["states"] == {"closed": 1}


# -- run_ladder -------------------------------------------------------------------
def test_run_ladder_degrades_in_order():
    calls = []

    def fail(level):
        def f():
            calls.append(level)
            raise RuntimeError(level)
        return f

    level, result = run_ladder("k", [("full", fail("full")),
                                     ("cheap", fail("cheap")),
                                     ("ref", lambda: "floor")])
    assert (level, result) == ("ref", "floor")
    assert calls == ["full", "cheap"]
    g = telemetry().snapshot()["guard"]
    assert g["degradations"] == {"ref": 1}
    assert g["degradation_triggers"] == {"init:RuntimeError": 1}
    assert g["guard_failures"] == {"full:init:RuntimeError": 1,
                                   "cheap:init:RuntimeError": 1}


def test_run_ladder_floor_reraises():
    def f():
        raise ValueError("x")
    with pytest.raises(ValueError):
        run_ladder("k", [("full", f), ("ref", f)])


# -- the pipeline ladder end to end -----------------------------------------------
def test_ladder_cheap_on_injected_rule_failure():
    prog = PROGRAMS["residual_scale"]()
    with chaos.plan_scope(chaos.FaultPlan(sites=("rule_raise",),
                                          max_fires=1)):
        sk = saturate_program(prog, _base_cfg())
    assert sk.ladder_level == "cheap"
    guard = telemetry().snapshot()["guard"]
    assert guard["degradations"].get("cheap") == 1
    assert guard["degradation_triggers"].get("chaos:rule_raise") == 1
    assert guard["ladder_levels"].get("cheap") == 1


def test_ladder_ref_floor_on_codegen_failure():
    prog = PROGRAMS["residual_scale"]()
    x = np.random.default_rng(0).uniform(
        0.1, 1, (8, 128)).astype(np.float32)
    y = np.random.default_rng(1).uniform(
        0.1, 1, (8, 128)).astype(np.float32)
    with chaos.plan_scope(chaos.FaultPlan(sites=("exec_fail",),
                                          max_fires=None)):
        op = make_tile_op(prog, _base_cfg())
        out = op.apply(jnp.asarray(x), jnp.asarray(y), alpha=0.5)
    assert op.sk.ladder_level == "ref"
    assert op.pk is None           # no Pallas kernel on the floor
    np.testing.assert_allclose(np.asarray(out), x + 0.5 * y, rtol=1e-6)


def test_saturate_breaker_opens_then_recovers():
    cfg = _base_cfg(guard_cfg=GuardConfig(breaker_threshold=2,
                                          breaker_cooldown=2))
    with chaos.plan_scope(chaos.FaultPlan(sites=("exec_fail",),
                                          max_fires=None)):
        for _ in range(2):
            sk = saturate_program(PROGRAMS["residual_scale"](), cfg)
            assert sk.ladder_level == "ref"
    # breaker open: even fault-free calls skip to the recorded rung
    sk = saturate_program(PROGRAMS["residual_scale"](), cfg)
    assert sk.ladder_level == "ref"
    guard = telemetry().snapshot()["guard"]
    assert guard["breaker_events"].get("open", 0) >= 1
    assert guard["breaker_events"].get("skip", 0) >= 1
    # cool-down spent: the half-open trial runs the full path and closes
    sk = saturate_program(PROGRAMS["residual_scale"](), cfg)
    assert sk.ladder_level == "cold"
    assert telemetry().snapshot()["guard"]["breaker_events"] \
        .get("close", 0) >= 1


def test_guard_config_not_in_cache_fingerprint():
    prog = PROGRAMS["rmsnorm"]()
    k1 = cache_key_for(prog, SaturatorConfig())
    k2 = cache_key_for(prog, SaturatorConfig(
        guard_cfg=GuardConfig(eval_budget=7, deadline_s=1.0,
                              breaker_threshold=1)))
    assert k1.exact_key == k2.exact_key
    assert k1.warm_key == k2.warm_key


# -- cache store under filesystem faults ------------------------------------------
def _store_fixture(tmp_path):
    prog = PROGRAMS["rmsnorm"]()
    key = cache_key_for(prog, SaturatorConfig())
    cache = SaturationCache(tmp_path / "root")
    entry = make_entry(key, choice_doc={"roots": []}, schedule_doc=None,
                       predicted=None, dag_cost=1.0, report={})
    return cache, key, entry


def test_cache_put_enospc_disables_cache(tmp_path):
    cache, key, entry = _store_fixture(tmp_path)
    with chaos.plan_scope(chaos.FaultPlan(sites=("cache_write_io",),
                                          max_fires=None)):
        assert cache.put(key, entry) is False
        assert cache._usable is False
        # disabled for the process: the next put never reaches the
        # write path (the injected fault does not fire again)
        assert cache.put(key, entry) is False
        assert chaos.fire_counts() == {"cache_write_io": 1}
    snap = telemetry().snapshot()
    assert snap["cache_invalid"] >= 1
    assert any("cache write failed" in e.get("reason", "")
               for e in telemetry().events if e["kind"] == "cache_invalid")
    assert not list((tmp_path / "root").rglob("*.json"))   # nothing torn


def test_cache_read_fault_degrades_to_miss(tmp_path):
    cache, key, entry = _store_fixture(tmp_path)
    assert cache.put(key, entry) is True
    doc, status = cache.lookup(key)
    assert status == "hit" and doc is not None
    with chaos.plan_scope(chaos.FaultPlan(sites=("cache_read_io",),
                                          max_fires=None)):
        doc, status = cache.lookup(key)
    assert status == "miss" and doc is None
    assert telemetry().snapshot()["cache_invalid"] >= 1
    # the volume recovered: the entry is still intact on disk
    doc, status = cache.lookup(key)
    assert status == "hit"


def test_cache_corrupt_entry_rejected_by_digest(tmp_path):
    cache, key, entry = _store_fixture(tmp_path)
    assert cache.put(key, entry) is True
    with chaos.plan_scope(chaos.FaultPlan(sites=("cache_corrupt",),
                                          max_fires=None)):
        doc, status = cache.lookup(key)
    assert status == "miss" and doc is None


# -- ops-layer runtime floor -------------------------------------------------------
def test_ops_layer_never_raises(monkeypatch):
    from repro.kernels import ops
    from repro.kernels import ref as kref

    def boom(*a, **k):
        raise RuntimeError("build exploded")

    monkeypatch.setattr(ops, "get_tile_op", boom)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0.1, 1, (8, 128)).astype(np.float32))
    g = jnp.ones((1, 128), jnp.float32)
    for _ in range(4):
        out = ops.rmsnorm(x, g)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(kref.rmsnorm_ref(x, g)),
                               rtol=1e-6)
    guard = telemetry().snapshot()["guard"]
    assert guard["runtime_fallbacks"].get("rmsnorm") == 4
    # after threshold consecutive failures the breaker skips the build
    assert breaker_for(("apply", "rmsnorm")).state == "open"
    assert guard["breaker_events"].get("open", 0) >= 1


# -- ft.py: injector unification + straggler policy + recovery ---------------------
def test_failure_injector_unified_with_chaos():
    inj = FailureInjector({3: ("node_loss", 2)})
    inj.check(0)
    with pytest.raises(FailureEvent) as ei:
        inj.check(3)
    assert (ei.value.kind, ei.value.lost_hosts) == ("node_loss", 2)
    inj.check(3)                       # one-shot
    assert inj.fired == [3]
    assert telemetry().snapshot()["guard"]["chaos_fires"] \
        .get("train_host_loss") == 1
    # an ambient chaos plan can drive host loss with no step schedule
    with chaos.plan_scope(chaos.FaultPlan(sites=("train_host_loss",),
                                          max_fires=1)):
        inj2 = FailureInjector()
        with pytest.raises(FailureEvent) as ei:
            inj2.check(0)
        assert ei.value.kind == "chaos_host_loss"
        inj2.check(1)                  # max_fires spent


def _mini_trainer(tmp_path, steps=6, inject=None, **loop_kw):
    cfg = TrainLoopConfig(total_steps=steps, ckpt_every=2,
                          ckpt_dir=str(tmp_path / "ckpt"), **loop_kw)

    def build_step(n_shards):
        class Pipe:
            def batch_at(self, step):
                return {"step": np.asarray(float(step))}

        def step(params, opt_state, batch):
            return params + 1.0, opt_state, float(batch["step"])

        return step, Pipe()

    return ElasticTrainer(cfg, build_step, np.zeros(2, np.float32),
                          {"m": np.zeros(2, np.float32)}, num_shards=2,
                          injector=FailureInjector(inject))


def test_straggler_policy_tracking(tmp_path):
    tr = _mini_trainer(tmp_path, steps=2,
                       straggler=StragglerPolicy(factor=2.0, patience=2,
                                                 ewma=0.1))
    tr._track_straggler(0.1)            # seeds the EWMA
    assert tr._ewma_time == pytest.approx(0.1)
    tr._track_straggler(0.5)            # slow: streak 1, EWMA frozen
    assert tr._slow_streak == 1
    assert tr._ewma_time == pytest.approx(0.1)
    tr._track_straggler(0.5)            # patience hit: degrade + reset
    assert tr._slow_streak == 0
    assert tr.elastic_events[-1]["kind"] == "straggler_degrade"
    tr._track_straggler(0.12)           # fast again: EWMA moves
    assert tr._ewma_time == pytest.approx(0.9 * 0.1 + 0.1 * 0.12)
    assert sum(1 for e in tr.log if e["straggler"]) == 2


def test_straggler_clock_covers_the_loss_sync(tmp_path):
    class SlowLoss:                     # reaches the host 20 ms late
        def __float__(self):
            time.sleep(0.02)
            return 1.0

    def build_step(n_shards):
        class Pipe:
            def batch_at(self, step):
                return {}

        return (lambda params, opt, batch: (params, opt, SlowLoss())), Pipe()

    cfg = TrainLoopConfig(total_steps=3, ckpt_every=100,
                          ckpt_dir=str(tmp_path / "ckpt"))
    tr = ElasticTrainer(cfg, build_step, np.zeros(2, np.float32),
                        {"m": np.zeros(2, np.float32)}, num_shards=1)
    assert tr.run()["losses"] == [1.0] * 3
    # the first step seeds the EWMA; the others are logged
    assert len(tr.log) == 2
    assert all(e["dt"] >= 0.02 for e in tr.log)


def test_recovery_preserves_saturation_settings(tmp_path):
    from repro.kernels import ops
    prev = (ops.current_saturation_cache(), ops.current_saturation_verify())
    try:
        sat_dir = str(tmp_path / "sat")
        ops.set_saturation_cache(sat_dir)
        ops.set_saturation_verify("cheap")
        tr = _mini_trainer(tmp_path, steps=6,
                           inject={3: ("node_loss", 1)})
        # a replacement host boots with process defaults — recovery
        # must re-apply the run's snapshot, not inherit these
        ops.set_saturation_cache(None)
        ops.set_saturation_verify(None)
        out = tr.run()
        assert out["recoveries"] == 1 and out["final_step"] == 6
        assert ops.current_saturation_cache() == sat_dir
        assert ops.current_saturation_verify() == "cheap"
        snap = telemetry().snapshot()["guard"]
        assert snap["elastic_recoveries"] == 1
    finally:
        ops.set_saturation_cache(prev[0])
        ops.set_saturation_verify(prev[1])


@pytest.mark.slow
def test_simulate_host_restart_clears_tile_ops(tmp_path):
    get_tile_op("l2_clip")
    assert get_tile_op.cache_info().currsize >= 1
    tr = _mini_trainer(tmp_path, steps=4, inject={2: ("node_loss", 1)},
                       simulate_host_restart=True)
    out = tr.run()
    assert out["recoveries"] == 1
    # the replacement host starts with no in-process tile ops; the
    # persistent cache (if configured) is what makes it warm again
    assert get_tile_op.cache_info().currsize == 0


# -- concurrent serving under cache faults -----------------------------------------
@pytest.mark.slow
def test_server_hammer_under_cache_faults(tmp_path):
    from repro.kernels import ops
    from repro.launch.serve import Request, Server
    prev = ops.current_saturation_cache()
    try:
        sat_dir = str(tmp_path / "sat")
        ops.set_saturation_cache(sat_dir)
        get_tile_op.cache_clear()
        srv = Server("mamba2-1.3b", smoke=True, max_batch=2)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, srv.cfg.vocab,
                                size=12).astype(np.int32)
                   for _ in range(8)]
        baseline = {}
        for i, p in enumerate(prompts):
            baseline[i] = srv.generate(
                [Request(rid=i, prompt=p, max_new=4)])[i]

        # rebuild every tile op mid-flight, with reads of the (now
        # populated) cache failing half the time, under 8 threads
        get_tile_op.cache_clear()
        telemetry().reset()
        reset_breakers()
        chaos.install_plan(chaos.FaultPlan(
            sites=("cache_read_io",), max_fires=None,
            probability=0.5, seed=7))
        results, errors = {}, []

        def worker(i):
            try:
                out = srv.generate(
                    [Request(rid=100 + i, prompt=prompts[i], max_new=4)])
                results[i] = out[100 + i]
            except Exception as e:   # noqa: BLE001 — the assertion target
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        chaos.clear_plan()

        assert errors == []
        for i in range(8):   # every response correct despite the faults
            assert results[i] == baseline[i], f"request {i} diverged"
        assert srv.metrics["prefills"] == 16     # no lost increments
        snap = telemetry().snapshot()
        guard = snap["guard"]
        assert all(isinstance(v, int) and v >= 0
                   for v in guard["chaos_fires"].values())
        bs = breakers_snapshot()
        assert sum(bs["states"].values()) == bs["total"]
        # cache faults degrade below the ladder: no breaker ever opened
        assert bs["states"].get("open", 0) == 0
        assert guard["breaker_events"].get("open", 0) == 0
        # the metrics snapshot itself is attached and well-formed
        assert "guard" in srv.metrics["saturation"]
    finally:
        chaos.clear_plan()
        ops.set_saturation_cache(prev)
        get_tile_op.cache_clear()


# -- lazy runtime facade -----------------------------------------------------------
def test_runtime_package_lazy_exports():
    import repro.runtime as rt
    assert rt.SaturationGuard is SaturationGuard
    assert rt.FaultPlan is chaos.FaultPlan
    assert rt.ElasticTrainer is ElasticTrainer
    with pytest.raises(AttributeError):
        rt.definitely_not_a_name
