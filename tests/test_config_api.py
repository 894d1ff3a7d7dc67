"""PR-8 grouped SaturatorConfig API.

Pins the three compatibility contracts of the config split:

* grouped sub-configs and the deprecated flat kwargs build *equal*
  configs (and the flat path warns);
* ``config_fingerprint`` is byte-identical to the pre-split digests —
  golden hashes below were captured from the flat-kwarg constructor, so
  no persistent-cache entry invalidates;
* ``from_env`` is the one front door for the cache/verify side-channels
  with pinned precedence: explicit argument > CLI flag > environment
  variable > default.
"""
import argparse
import dataclasses
import warnings

import pytest

from repro.cache import cache_key_for, config_fingerprint
from repro.core import (CACHE_ENV_VAR, CacheConfig, SaturatorConfig,
                        ScheduleConfig, SearchConfig, VerifyConfig)
from repro.core.pipeline import VERIFY_ENV_VAR


# -- golden fingerprints (captured pre-split; must never drift) -------------
GOLDEN_FINGERPRINTS = {
    "default": (
        SaturatorConfig(),
        "383612fda9e1355840552031f6eb54a22605f6ddb17e694d61733a492efa04b1"),
    "tile_default": (
        SaturatorConfig(mode="accsat", cost_model="tpu_v5e",
                        tpu_rules=True),
        "707dc211eaacc1d04bb1c02501c56b42e3f70bd340a5ec5e49b7a4aee32de6be"),
    "tile_cost": (
        SaturatorConfig(mode="accsat", cost_model="tpu_v5e", tpu_rules=True,
                        schedule_cfg=ScheduleConfig(schedule="cost")),
        "4ff486817a2ba6ce15ea9d5939bde0051901274c1135850cf86968a70ecefbfb"),
    "cse": (
        SaturatorConfig(mode="cse",
                        schedule_cfg=ScheduleConfig(schedule="source"),
                        verify_cfg=VerifyConfig(verify="cheap")),
        "d556827b85e37ddbd6c28f95e59a2515724a2d4a6f9d6e27b7947acccf6ac197"),
    "beam_tweak": (
        SaturatorConfig(search_cfg=SearchConfig(beam_width=4,
                                                beam_expansions=500,
                                                hillclimb_evals=1000,
                                                local_search=False,
                                                search="hillclimb")),
        "ba571c01755ee13dfcc8983f634356439cd123177dad70f58c2cd7cf75b6c807"),
}


@pytest.mark.parametrize("case", sorted(GOLDEN_FINGERPRINTS))
def test_config_fingerprint_golden(case):
    cfg, want = GOLDEN_FINGERPRINTS[case]
    assert config_fingerprint(cfg) == want


def test_cache_key_golden():
    from repro.kernels.tile_programs import PROGRAMS
    cfg = GOLDEN_FINGERPRINTS["tile_cost"][0]
    key = cache_key_for(PROGRAMS["rmsnorm"](), cfg)
    assert key.warm_key == \
        "bf7bc460b908a1427c0f0c62553dc3d3b3878d413b53a280a7c63be403e33fca"
    assert key.exact_key == \
        "eb75f71bde3a077c35a460f44c056b9c4b458474f1019567399389905d3689da"


# -- flat-kwarg compatibility -----------------------------------------------
def test_legacy_flat_kwargs_warn_and_build_equal_config():
    with pytest.warns(DeprecationWarning, match="flat SaturatorConfig"):
        legacy = SaturatorConfig(mode="accsat",
                                 schedule="cost",      # deprecated-ok
                                 beam_width=4,         # deprecated-ok
                                 cache_dir="/tmp/x",   # deprecated-ok
                                 verify="cheap")       # deprecated-ok
    grouped = SaturatorConfig(
        mode="accsat",
        search_cfg=SearchConfig(beam_width=4),
        schedule_cfg=ScheduleConfig(schedule="cost"),
        cache_cfg=CacheConfig(cache_dir="/tmp/x"),
        verify_cfg=VerifyConfig(verify="cheap"))
    assert legacy == grouped
    assert config_fingerprint(legacy) == config_fingerprint(grouped)


def test_flat_read_properties_mirror_groups():
    cfg = SaturatorConfig(
        search_cfg=SearchConfig(iter_limit=3, beam_width=2),
        schedule_cfg=ScheduleConfig(schedule="cost", emitter="pallas"),
        cache_cfg=CacheConfig(cache_dir="/tmp/c", cache_warm_start=False),
        verify_cfg=VerifyConfig(verify="full"))
    assert cfg.iter_limit == 3
    assert cfg.beam_width == 2
    assert cfg.schedule == "cost"
    assert cfg.emitter == "pallas"
    assert cfg.cache_dir == "/tmp/c"
    assert cfg.cache_warm_start is False
    assert cfg.verify == "full"


def test_unknown_kwarg_still_typeerror():
    with pytest.raises(TypeError, match="unexpected keyword"):
        SaturatorConfig(bogus_knob=1)


def test_emitter_is_first_class_not_deprecated():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg = SaturatorConfig(emitter="pallas")
    assert cfg.emitter == "pallas"
    assert cfg.schedule_cfg.emitter == "pallas"


def test_dataclasses_replace_on_groups():
    base = SaturatorConfig(mode="accsat")
    tweaked = dataclasses.replace(
        base, search_cfg=dataclasses.replace(base.search_cfg, beam_width=2))
    assert tweaked.beam_width == 2
    assert tweaked.mode == "accsat"
    assert base.beam_width == SearchConfig().beam_width


def test_group_validation_still_applies():
    with pytest.raises(ValueError, match="schedule"):
        SaturatorConfig(schedule_cfg=ScheduleConfig(schedule="zigzag"))
    with pytest.raises(ValueError, match="verify"):
        SaturatorConfig(verify_cfg=VerifyConfig(verify="paranoid"))
    with pytest.raises(ValueError, match="search"):
        SaturatorConfig(search_cfg=SearchConfig(search="genetic"))


# -- from_env precedence ----------------------------------------------------
def _flags(**kw):
    ns = argparse.Namespace(cache_dir=None, no_cache=False, verify=None)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_from_env_default_is_off():
    cfg = SaturatorConfig.from_env(env={})
    assert cfg.cache_dir is None
    assert cfg.verify == "off"


def test_from_env_env_var_level():
    env = {CACHE_ENV_VAR: "/env/cache", VERIFY_ENV_VAR: "cheap"}
    cfg = SaturatorConfig.from_env(env=env)
    assert cfg.cache_dir == "/env/cache"
    assert cfg.verify == "cheap"


def test_from_env_flag_beats_env():
    env = {CACHE_ENV_VAR: "/env/cache", VERIFY_ENV_VAR: "cheap"}
    cfg = SaturatorConfig.from_env(
        flags=_flags(cache_dir="/flag/cache", verify="full"), env=env)
    assert cfg.cache_dir == "/flag/cache"
    assert cfg.verify == "full"


def test_from_env_explicit_beats_flag_and_env():
    env = {CACHE_ENV_VAR: "/env/cache", VERIFY_ENV_VAR: "cheap"}
    cfg = SaturatorConfig.from_env(
        cache_dir="/arg/cache", verify="off",
        flags=_flags(cache_dir="/flag/cache", verify="full"), env=env)
    assert cfg.cache_dir == "/arg/cache"
    assert cfg.verify == "off"


def test_from_env_no_cache_disables_even_with_env():
    env = {CACHE_ENV_VAR: "/env/cache"}
    cfg = SaturatorConfig.from_env(
        flags=_flags(cache_dir="/flag/cache", no_cache=True), env=env)
    assert cfg.cache_dir is False      # resolved --no-cache: cache off
    assert (cfg.cache_dir or None) is None


def test_from_env_accepts_mapping_flags_and_kwargs():
    cfg = SaturatorConfig.from_env(
        flags={"verify": "cheap"}, env={}, mode="cse",
        schedule_cfg=ScheduleConfig(schedule="source"))
    assert cfg.mode == "cse"
    assert cfg.schedule == "source"
    assert cfg.verify == "cheap"


def test_drivers_use_from_env():
    """Both launch drivers resolve their side-channels through the one
    front door (regression guard for ad-hoc os.environ reads)."""
    import inspect
    from repro.launch import serve, train
    assert "from_env" in inspect.getsource(serve.main)
    assert "from_env" in inspect.getsource(train.main)


def test_deprecation_lint_clean():
    """The repo's own code never uses the deprecated flat kwargs or the
    pre-registry generator class names (the CI lint step, run in-tree)."""
    import pathlib
    import subprocess
    import sys
    script = pathlib.Path(__file__).resolve().parents[1] / "tools" / \
        "deprecation_lint.py"
    out = subprocess.run([sys.executable, str(script)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr + out.stdout
