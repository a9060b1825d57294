"""Tier dispatcher for the Pallas kernels.

One registry maps each kernel name to its implementations per tier:

    ``tpu``       — compiled Pallas kernel (TPU backend attached)
    ``interpret`` — the TPU Pallas kernel under the interpreter
                    (CPU hosts: validates kernel numerics, slowly)
    ``ref``       — the pure-jnp oracle from :mod:`repro.kernels.ref`

The process tier is resolved once by :func:`repro.compat.kernel_tier`
(``tpu`` on a TPU backend, ``interpret`` elsewhere; overridable via the
``REPRO_KERNEL_TIER`` env var or :func:`repro.compat.set_kernel_tier`).
Every kernel registers every tier; resolving a tier a kernel lacks
raises instead of substituting another.

Model hot paths use :func:`model_tier` instead of the raw process tier:
an explicit override is honored verbatim; otherwise a TPU backend runs
the ``tpu`` kernels and any other backend the ``ref``/XLA path — the
interpreter is a numerics validation vehicle, orders of magnitude too
slow for model-sized calls.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from repro import compat


class KernelDispatcher:
    """Name -> {tier -> impl} registry."""

    def __init__(self):
        self._impls: Dict[str, Dict[str, Callable]] = {}

    def register(self, name: str, tier: str, fn: Callable) -> Callable:
        if tier not in compat.KERNEL_TIERS:
            raise ValueError(f"unknown tier {tier!r}; "
                             f"expected one of {compat.KERNEL_TIERS}")
        self._impls.setdefault(name, {})[tier] = fn
        return fn

    def kernels(self) -> Tuple[str, ...]:
        return tuple(sorted(self._impls))

    def registered_tiers(self, name: str) -> Tuple[str, ...]:
        return tuple(t for t in compat.KERNEL_TIERS
                     if t in self._impls.get(name, {}))

    def resolve(self, name: str,
                tier: Optional[str] = None) -> Tuple[str, Callable]:
        """(tier, impl) for ``name``. ``tier=None`` uses the process
        tier; a tier the kernel does not register raises."""
        try:
            impls = self._impls[name]
        except KeyError:
            raise KeyError(f"no kernel named {name!r}; "
                           f"registered: {self.kernels()}") from None
        tier = tier or compat.kernel_tier()
        if tier not in impls:
            raise KeyError(
                f"kernel {name!r} has no {tier!r} tier; "
                f"registered tiers: {self.registered_tiers(name)}")
        return tier, impls[tier]

    def call(self, name: str, *args, tier: Optional[str] = None, **kwargs):
        _, fn = self.resolve(name, tier)
        return fn(*args, **kwargs)


DISPATCHER = KernelDispatcher()


def register(name: str, tier: str):
    """Decorator: register ``fn`` as the ``tier`` impl of ``name``."""
    def deco(fn: Callable) -> Callable:
        return DISPATCHER.register(name, tier, fn)
    return deco


def coerce_tier(tier: Optional[str], interpret: Optional[bool]) -> Optional[str]:
    """Back-compat: the pre-dispatcher API took ``interpret: bool``."""
    if tier is not None:
        return tier
    if interpret is None:
        return None
    return "interpret" if interpret else "tpu"


def model_tier() -> str:
    """Dispatch tier for model hot paths (forward/decode under jit).

    Explicit override (env/config) wins, honored verbatim; otherwise
    ``tpu`` on a TPU backend and ``ref`` on any other — never
    ``interpret`` unless asked for.
    """
    explicit = compat.explicit_kernel_tier()
    if explicit is not None:
        return explicit
    return "tpu" if compat.is_tpu_backend() else "ref"
