"""PR-8 emitter registry: one front door for every code generator.

Before this module the two emission paths were a subclass fork
(``CodeGenerator`` for JAX source, ``PallasGenerator`` for Pallas kernel
bodies) that callers imported directly. Instead, emitters are now named:

==========  =============================  ==================
name        generator                      produces
==========  =============================  ==================
``jax``     :class:`JaxCodeGenerator`      ``GeneratedKernel``
``pallas``  :class:`SyncPallasGenerator`   ``PallasKernel``
==========  =============================  ==================

``get_emitter(name)`` returns a small :class:`Emitter` facade; its
``emit(ssa, extraction, **options)`` classmethod builds the generator
and runs it, and ``info`` carries the registry metadata — including the
``version`` that enters the cache key for non-default emitters (see
:func:`emitter_cache_id` and ``repro.cache.keys.config_fingerprint``).

The pre-registry class names (``CodeGenerator``, ``PallasGenerator``)
remain importable as deprecated aliases; the CI deprecation lint keeps
the repo's own code off them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

EMITTER_NAMES: Tuple[str, ...] = ("jax", "pallas")

# Bump an emitter's version whenever its emitted source for a fixed
# (choice, schedule) changes: non-default emitters carry name@version in
# the cache config fingerprint, so cached replays never mix emitters.
_VERSIONS: Dict[str, int] = {"jax": 1, "pallas": 1}

# Emitters whose cache entries predate the registry: their fingerprints
# must stay byte-identical, so they contribute *no* emitter key (None).
_DEFAULT_EMITTERS = (None, "jax", "pallas")


@dataclasses.dataclass(frozen=True)
class EmitterInfo:
    name: str      # registry name
    version: int   # cache-key version (see _VERSIONS)
    target: str    # "jax" (GeneratedKernel) or "pallas" (PallasKernel)


class Emitter:
    """Facade over one generator class.

    ``emit`` accepts the common generator options (``bulk``,
    ``fn_name``, ``reuse_temps``, ``schedule``, ``sched_cost_model`` and,
    for the jax target, ``extra_fns``) and returns the generator's
    product — a ``GeneratedKernel`` or ``PallasKernel``.
    """

    info: EmitterInfo

    # resolved lazily: the generator modules import this one's clients
    @property
    def generator_cls(self):
        raise NotImplementedError

    def emit(self, ssa, extraction, **options):
        gen = self.generator_cls(ssa, extraction, **options)
        if self.info.target == "pallas":
            return gen.generate_pallas()
        return gen.generate()


class _JaxEmitter(Emitter):
    info = EmitterInfo("jax", _VERSIONS["jax"], "jax")

    @property
    def generator_cls(self):
        from .codegen import JaxCodeGenerator
        return JaxCodeGenerator


class _PallasEmitter(Emitter):
    info = EmitterInfo("pallas", _VERSIONS["pallas"], "pallas")

    @property
    def generator_cls(self):
        from .pallasgen import SyncPallasGenerator
        return SyncPallasGenerator


_REGISTRY: Dict[str, Emitter] = {
    "jax": _JaxEmitter(),
    "pallas": _PallasEmitter(),
}


def get_emitter(name: str) -> Emitter:
    """The registered emitter, by name (``EMITTER_NAMES``)."""
    em = _REGISTRY.get(name)
    if em is None:
        raise ValueError(f"unknown emitter {name!r}; "
                         f"expected one of {EMITTER_NAMES}")
    return em


def emitter_cache_id(name: Optional[str]) -> Optional[str]:
    """The ``name@v{version}`` token a config fingerprint carries for a
    non-default emitter, or None for the pre-registry defaults (whose
    cached entries must keep their byte-identical keys)."""
    if name in _DEFAULT_EMITTERS:
        return None
    if name not in _VERSIONS:
        raise ValueError(f"unknown emitter {name!r}; "
                         f"expected one of {EMITTER_NAMES}")
    return f"{name}@v{_VERSIONS[name]}"
