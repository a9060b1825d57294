"""Backend capability, compile counting and kernel-tier resolution.

The repository runs on one installation (jax/jaxlib 0.9.0, libtpu
0.0.34): JAX and Pallas surfaces are called directly wherever they are
used. What lives here is what the program has to *decide* about the
host it runs on:

* **Compile counting** — a process-wide XLA compile counter riding
  ``jax.monitoring`` backend-compile events (:func:`compile_events` /
  :class:`CompileCounter`): the proof that SubNetAct actuation never
  recompiles (serving/executor.py).
* **Donation** — whether buffer donation is honored on this backend
  (:func:`donation_works`, a real donated round trip).
* **Compile cache** — :func:`enable_compile_cache`, called by entry
  points (never at import) to keep JAX's persistent compilation cache
  in one fixed place.

Kernel dispatch tiers
---------------------
The Pallas kernels run at one of three tiers (see
:mod:`repro.kernels.dispatch`):

    ``tpu``       — compiled Pallas kernels on a TPU backend
    ``interpret`` — the same kernels under the Pallas interpreter
                    (CPU tests: validates kernel numerics without a TPU)
    ``ref``       — the pure-jnp oracles in :mod:`repro.kernels.ref`

The process tier follows the platform: ``tpu`` on a TPU backend,
``interpret`` elsewhere. There is no probed fallback: a tier the host
cannot run is an error. Override with ``REPRO_KERNEL_TIER=tpu|interpret|
ref`` or :func:`set_kernel_tier`.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = [
    "KERNEL_TIERS",
    "backend",
    "is_tpu_backend",
    "compile_events",
    "CompileCounter",
    "donation_works",
    "enable_compile_cache",
    "tier_available",
    "kernel_tier",
    "explicit_kernel_tier",
    "set_kernel_tier",
    "reset_kernel_tier",
]


# --------------------------------------------------------------------------
# Compile counting and donation
# --------------------------------------------------------------------------

_compile_events = 0
_compile_listener_installed = False


def _note_compile_event(event: str, *args, **kwargs) -> None:
    """jax.monitoring duration listener. Only backend (XLA) compilations
    are counted — jaxpr tracing and MLIR lowering re-run cheaply on
    cache hits too."""
    global _compile_events
    if "backend_compile" in event:
        _compile_events += 1


def _install_compile_listener() -> None:
    global _compile_listener_installed
    if not _compile_listener_installed:
        jax.monitoring.register_event_duration_secs_listener(
            _note_compile_event)
        _compile_listener_installed = True


def compile_events() -> int:
    """Monotone count of XLA backend compilations in this process.

    This is the SubNetAct enforcement probe: serving code asserts the
    count does NOT move across subnet actuations (control tuples are
    traced data, never part of the jit cache key)."""
    _install_compile_listener()
    return _compile_events


class CompileCounter:
    """``with CompileCounter() as cc: ...; cc.count`` — XLA backend
    compilations during the block."""

    def __init__(self):
        _install_compile_listener()
        self._start = 0
        self.count = 0

    def __enter__(self) -> "CompileCounter":
        self._start = _compile_events
        return self

    def __exit__(self, *exc) -> None:
        self.count = _compile_events - self._start


_donation_probe: Optional[bool] = None


def donation_works() -> bool:
    """Probe (once) whether buffer donation is honored on this backend:
    an actual donated round trip, checking that the input buffer was
    consumed."""
    global _donation_probe
    if _donation_probe is None:
        import jax.numpy as jnp
        f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        x = jnp.ones((8,), jnp.float32)
        jax.block_until_ready(f(x))
        _donation_probe = bool(x.is_deleted())
    return _donation_probe


# --------------------------------------------------------------------------
# Persistent compilation cache
# --------------------------------------------------------------------------

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout (and in .gitignore): the cache key includes
# the path, so a directory that moves between runs would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    it stays in charge. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout. Entry points call this; importing the
    package never does."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


# --------------------------------------------------------------------------
# Backend + kernel tier resolution
# --------------------------------------------------------------------------

KERNEL_TIERS = ("tpu", "interpret", "ref")
_TIER_ENV = "REPRO_KERNEL_TIER"
_tier_cache: Optional[str] = None
_explicit_tier: Optional[str] = None


def backend() -> str:
    return jax.default_backend()


def is_tpu_backend() -> bool:
    return backend() == "tpu"


def tier_available(tier: str) -> bool:
    """Whether a dispatch tier can execute on this host: compiled Pallas
    TPU kernels need a TPU backend; the interpreter and the oracles run
    anywhere."""
    if tier not in KERNEL_TIERS:
        return False
    return tier != "tpu" or is_tpu_backend()


def _env_tier() -> Optional[str]:
    env = os.environ.get(_TIER_ENV, "").strip().lower()
    if not env:
        return None
    if env not in KERNEL_TIERS:
        raise ValueError(
            f"{_TIER_ENV}={env!r}: expected one of {KERNEL_TIERS}")
    if not tier_available(env):
        raise RuntimeError(
            f"{_TIER_ENV}={env!r} requested but that tier is not "
            f"available on this host (backend={backend()!r})")
    return env


def kernel_tier() -> str:
    """The process-wide kernel dispatch tier, resolved once: an explicit
    override, else ``tpu`` on a TPU backend and ``interpret``
    elsewhere."""
    global _tier_cache
    if _tier_cache is None:
        _tier_cache = _env_tier() or ("tpu" if is_tpu_backend()
                                      else "interpret")
    return _tier_cache


def explicit_kernel_tier() -> Optional[str]:
    """The tier the operator *asked* for (env var or set_kernel_tier),
    or None when the process tier follows the platform. Model hot paths
    use this to honor a forced tier."""
    if _explicit_tier is not None:
        return _explicit_tier
    return _env_tier()


def set_kernel_tier(tier: str) -> str:
    """Config override of the process tier (validated). Returns it."""
    global _tier_cache, _explicit_tier
    if tier not in KERNEL_TIERS:
        raise ValueError(f"unknown kernel tier {tier!r}; "
                         f"expected one of {KERNEL_TIERS}")
    if not tier_available(tier):
        raise RuntimeError(f"kernel tier {tier!r} unavailable on this host "
                           f"(backend={backend()!r})")
    _tier_cache = _explicit_tier = tier
    return tier


def reset_kernel_tier() -> None:
    """Drop the cached/explicit tier (re-resolves on next use)."""
    global _tier_cache, _explicit_tier
    _tier_cache = _explicit_tier = None
