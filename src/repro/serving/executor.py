"""Compiled-path subnet executor: AOT-warmed, shape-bucketed real
execution behind the serving plane.

The paper's core claim is that SubNetAct actuates any point in the
latency-accuracy space *near-instantaneously* because switching subnets
is a control-tuple change, not a model load. This module is that claim
as an execution layer:

* **Traced-control actuation** — one jitted step wraps
  ``models/lm.forward``/``prefill``/``decode_step`` with the *stacked*
  control tuples and the subnet index passed as traced data. The jit
  cache is keyed on shapes only, so actuating a different subnet never
  recompiles (enforced by the ``compat.CompileCounter`` probe in
  tests/test_executor.py and benchmarks/bench_executor.py).
* **Shape buckets** — raw ``(batch, seq)`` shapes are right-padded up
  to configured power-of-two buckets, so the jit cache is bounded by
  the bucket lattice instead of growing with every distinct request
  shape. Right-padding is exact, not approximate: every LM family here
  is causal, so positions ``< length`` never see the pad, and the
  final-position logits are gathered at each row's true ``length - 1``
  (a traced index — no recompile per length).
* **Bounded cache** — compiled executables live in an LRU keyed
  ``(kind, bucket_batch, bucket_seq, tier)`` with an eviction cap and
  hit/miss/compile/eviction counters (surfaced via
  ``Router.stats()["executor"]``).
* **AOT lattice warmup** — :meth:`SubnetExecutor.warmup` pre-compiles
  every bucket the profiler says the policy can choose
  (``jit(...).lower(...).compile()``), off the serving critical path.
  A compile error raises where it happens. The first production query
  never pays XLA compile.
* **One device per executor** — params and the control stack are
  committed to ``device`` (default: the first local device) and every
  bucket compiles for it, so one process can host one executor per
  local chip (``launch/serve.py --execute real --replicas N``).
* **Buffer donation** — the decode cache is donated back to XLA where
  ``compat.donation_works()`` says the backend honors it, so steady
  decode runs in place instead of reallocating the KV cache per step.

Layering rule: the executor is pure *execution* — it owns compiled
artifacts, padding, and counters, and nothing else. Scheduling stays in
``serving/engine.py``; the executor plugs into the unchanged stack as
``make_supernet_workers`` workers (:meth:`make_workers`) and feeds
``profiler.measure_profile`` (:meth:`measured_profile`) so the engine /
policies / residency layers serve from *measured* latencies without
changing a line.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ArchConfig
from repro.core import subnet as sn
from repro.core.pareto import ParetoPoint, pareto_subnets
from repro.kernels.dispatch import model_tier
from repro.models import lm
from repro.serving import spans

__all__ = ["ExecutorConfig", "SubnetExecutor", "DecodeCache",
           "bucket_of", "init_params", "build_executor",
           "build_replica_executors", "build_serving_executor",
           "stacked_controls", "prefill_fn", "decode_fn"]


# the compiled steps; the ``executor.compile`` span's ``kind`` is an index
STEP_KINDS = ("prefill", "decode")


def bucket_of(n: int, buckets: Sequence[int]) -> int:
    """Smallest configured bucket >= ``n``; beyond the largest bucket,
    the next power of two (the cache still grows only log2-many keys,
    never one per raw shape)."""
    if n <= 0:
        raise ValueError(f"bucket_of: need n >= 1, got {n}")
    for b in buckets:
        if b >= n:
            return int(b)
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclass(frozen=True)
class ExecutorConfig:
    """Bucket lattice + cache policy for one :class:`SubnetExecutor`."""

    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    seq_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256)
    max_entries: int = 32               # LRU cap on compiled executables
    donate_cache: Optional[bool] = None  # None -> compat.donation_works()
    slice_mode: str = "mask"

    def __post_init__(self):
        for name in ("batch_buckets", "seq_buckets"):
            bs = getattr(self, name)
            if not bs or any(b <= 0 for b in bs) or list(bs) != sorted(bs):
                raise ValueError(f"{name} must be sorted positive ints, "
                                 f"got {bs}")
        if self.max_entries < 1:
            raise ValueError("max_entries must be >= 1")


@dataclass
class DecodeCache:
    """A bucketed KV/state cache plus the geometry it was built at.

    With donation enabled the underlying ``state`` is consumed by the
    decode step that receives it — keep only the cache the step
    returns."""

    batch: int                          # bucketed batch
    seq_cap: int                        # bucketed cache capacity
    state: Any = field(repr=False, default=None)


class SubnetExecutor:
    """Executes real subnet forward passes for the serving plane.

    One instance hosts one supernet (``params`` + ``cfg``) and the
    stacked control tuples of its Pareto subnets; every worker thread
    of a replica shares it (weight-shared, SubNetwork-stationary), so
    the compiled executables and their counters are process-global per
    supernet."""

    def __init__(self, params: Dict, cfg: ArchConfig,
                 points: Optional[Sequence[ParetoPoint]] = None,
                 exec_cfg: Optional[ExecutorConfig] = None, device=None):
        self.device = device if device is not None else jax.local_devices()[0]
        self.sharding = jax.sharding.SingleDeviceSharding(self.device)
        self.params = jax.device_put(params, self.device)
        self.cfg = cfg
        self.points: List[ParetoPoint] = list(points or pareto_subnets(cfg))
        # actuation == indexing this stack with a traced int32 — the
        # whole SubNetAct property hangs on ctrl being data, not shape
        self.stacked_ctrl = jax.device_put(
            stacked_controls(cfg, self.points), self.device)
        self.xcfg = exec_cfg or ExecutorConfig()
        self.donate = (self.xcfg.donate_cache
                       if self.xcfg.donate_cache is not None
                       else compat.donation_works())
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._lock = threading.RLock()
        self._counters = {"hits": 0, "misses": 0, "compiles": 0,
                          "evictions": 0}

    # -- introspection ---------------------------------------------------

    @property
    def n_subnets(self) -> int:
        return len(self.points)

    def accs(self) -> List[float]:
        return [p.acc for p in self.points]

    def counters(self) -> Dict[str, float]:
        """Hit/miss/compile/eviction counters plus current cache size
        (read via ``Router.stats()["executor"]`` on an executor-backed
        router)."""
        with self._lock:
            out = {k: float(v) for k, v in self._counters.items()}
            out["entries"] = float(len(self._cache))
            out["hit_rate"] = (out["hits"] / (out["hits"] + out["misses"])
                               if out["hits"] + out["misses"] else 0.0)
            return out

    def cache_keys(self) -> List[Tuple]:
        with self._lock:
            return list(self._cache.keys())

    # -- bucketed public steps -------------------------------------------

    def prefill(self, subnet_idx: int, tokens,
                lengths: Optional[Sequence[int]] = None) -> np.ndarray:
        """Final-position logits for a (B, S) int32 token batch.

        Pads to the (batch, seq) bucket, executes the compiled entry
        with the subnet index and per-row true lengths as traced data,
        and returns the (B, vocab) logits gathered at each row's last
        real position. Any (B, S) is accepted; only the bucket shape
        touches the jit cache."""
        tokens = np.asarray(tokens, dtype=np.int32)
        if tokens.ndim != 2:
            raise ValueError(f"prefill wants (B, S) tokens, "
                             f"got shape {tokens.shape}")
        B, S = tokens.shape
        Bb = bucket_of(B, self.xcfg.batch_buckets)
        Sb = bucket_of(S, self.xcfg.seq_buckets)
        with spans.span("executor.pad", rows=B, bucket_rows=Bb, seq=S,
                        bucket_seq=Sb):
            lens = np.full((Bb,), Sb, np.int32)
            lens[:B] = S if lengths is None else np.asarray(lengths, np.int32)
            if (Bb, Sb) != (B, S):
                padded = np.zeros((Bb, Sb), np.int32)
                padded[:B, :S] = tokens
                tokens = padded
        fn = self._get("prefill", Bb, Sb)
        dev = self.device.id
        with spans.span("executor.launch", device=dev):
            out = fn(self.params, self.stacked_ctrl, tokens,
                     np.int32(subnet_idx), lens)
        with spans.span("executor.device_wait", device=dev):
            out.block_until_ready()
        # host copy + host slice: a device-side out[:B] would compile a
        # tiny gather per (bucket, B) pair, breaking zero-compile serving
        with spans.span("executor.fetch", device=dev, bytes=out.nbytes):
            return np.asarray(out)[:B]

    def init_cache(self, batch: int, seq_cap: int) -> DecodeCache:
        """Fresh decode cache at the bucketed (batch, capacity)."""
        Bb = bucket_of(batch, self.xcfg.batch_buckets)
        Sb = bucket_of(seq_cap, self.xcfg.seq_buckets)
        state = jax.device_put(
            lm.init_cache(self.cfg, Bb, Sb, dtype=self.cfg.dtype), self.device)
        return DecodeCache(batch=Bb, seq_cap=Sb, state=state)

    def decode_step(self, subnet_idx: int, tokens, cache: DecodeCache,
                    index: int) -> Tuple[np.ndarray, DecodeCache]:
        """One decode step: (B, 1) int32 tokens against ``cache``.

        Returns ``(logits (B, vocab), new_cache)``. With donation on,
        ``cache.state`` is consumed in place — use the returned cache."""
        tokens = np.asarray(tokens, dtype=np.int32)
        B = tokens.shape[0]
        if B > cache.batch:
            raise ValueError(f"batch {B} exceeds cache batch {cache.batch}")
        if B < cache.batch:
            tokens = np.concatenate(
                [tokens, np.zeros((cache.batch - B, 1), np.int32)])
        fn = self._get("decode", cache.batch, cache.seq_cap)
        logits, state = fn(self.params, self.stacked_ctrl, tokens,
                           cache.state, np.int32(subnet_idx),
                           np.int32(index))
        return (np.asarray(logits)[:B, 0],
                DecodeCache(cache.batch, cache.seq_cap, state))

    # -- warmup ----------------------------------------------------------

    def warmup(self, batches: Optional[Sequence[int]] = None,
               seqs: Optional[Sequence[int]] = None,
               decode: bool = False) -> Dict[str, float]:
        """AOT-compile the bucket lattice off the serving critical path.

        ``batches`` defaults to the configured batch buckets — pass the
        profile's realizable batch sizes so exactly the buckets the
        policy can choose get compiled. Raises if the lattice exceeds
        the LRU cap (a warmed entry that is evicted before first use
        would silently put compilation back on the critical path)."""
        t0 = time.perf_counter()
        bbs = sorted({bucket_of(b, self.xcfg.batch_buckets)
                      for b in (batches or self.xcfg.batch_buckets)})
        sbs = sorted({bucket_of(s, self.xcfg.seq_buckets)
                      for s in (seqs or self.xcfg.seq_buckets[:1])})
        kinds = ("prefill", "decode") if decode else ("prefill",)
        lattice = [(k, b, s) for k in kinds for b in bbs for s in sbs]
        if len(lattice) > self.xcfg.max_entries:
            raise ValueError(
                f"warmup lattice of {len(lattice)} buckets exceeds "
                f"max_entries={self.xcfg.max_entries}; raise the cap or "
                f"shrink the lattice")
        compiled = 0
        for kind, b, s in lattice:
            before = self._counters["compiles"]
            self._get(kind, b, s)
            compiled += self._counters["compiles"] - before
        return {"n_buckets": float(len(lattice)),
                "n_compiled": float(compiled),
                "seconds": time.perf_counter() - t0}

    # -- serving-stack adapters ------------------------------------------

    def run_prefill(self, subnet_idx: int, batch) -> np.ndarray:
        """``step_fn`` for :func:`runtime.make_supernet_workers`:
        ``batch`` is the padded (B, S) token array; blocks on the
        result (worker threads hand numpy back to the event loop)."""
        return np.asarray(self.prefill(int(subnet_idx), batch))

    @staticmethod
    def pad_batch(payloads: List[Any]) -> np.ndarray:
        """``pad_batch`` for make_supernet_workers: stack token rows —
        padding to shape buckets happens inside the executor."""
        return np.stack([np.asarray(p, dtype=np.int32) for p in payloads])

    def make_workers(self, n: int):
        """``n`` WorkerHandles sharing this executor (weight-shared,
        one jit cache): the real-execution twin of the simulated
        service-time workers."""
        from repro.serving.runtime import make_supernet_workers
        return make_supernet_workers(n, self.run_prefill, self.pad_batch)

    def profile_step_fns(self, seq_len: int) -> List[Callable[[int], None]]:
        """Per-subnet ``fn(batch)`` closures for
        :func:`profiler.measure_profile` (each blocks on its result)."""
        def mk(i: int):
            return lambda b: self.run_prefill(
                i, np.ones((b, seq_len), np.int32))
        return [mk(i) for i in range(self.n_subnets)]

    def measured_profile(self, batches: Sequence[int] = (1, 2, 4, 8),
                         seq_len: int = 16, **kw):
        """Measured ``LatencyProfile`` over this executor's subnets —
        true wall-clock per (subnet, batch bucket) on this host, ready
        to drop into the unchanged engine/policy/residency stack. Run
        :meth:`warmup` first so measurement never times a compile."""
        from repro.serving.profiler import measure_profile
        return measure_profile(self.profile_step_fns(seq_len), self.accs(),
                               batches=tuple(batches), **kw)

    # -- compiled-entry cache --------------------------------------------

    def _get(self, kind: str, Bb: int, Sb: int) -> Callable:
        key = (kind, Bb, Sb, model_tier())
        with self._lock:
            fn = self._cache.get(key)
            if fn is not None:
                self._cache.move_to_end(key)
                self._counters["hits"] += 1
                return fn
            self._counters["misses"] += 1
            with spans.span("executor.compile", kind=STEP_KINDS.index(kind),
                            bucket_rows=Bb, bucket_seq=Sb):
                fn = self._build(kind, Bb, Sb)
            self._cache[key] = fn
            self._counters["compiles"] += 1
            while len(self._cache) > self.xcfg.max_entries:
                self._cache.popitem(last=False)
                self._counters["evictions"] += 1
            return fn

    def _build(self, kind: str, Bb: int, Sb: int) -> Callable:
        cfg, slice_mode = self.cfg, self.xcfg.slice_mode
        if kind == "prefill":
            jitted = jax.jit(prefill_fn(cfg, slice_mode))
            shaped = (self._shaped(self.params),
                      self._shaped(self.stacked_ctrl),
                      self._spec((Bb, Sb)), self._spec(()), self._spec((Bb,)))
        elif kind == "decode":
            jitted = jax.jit(decode_fn(cfg, slice_mode),
                             donate_argnums=(3,) if self.donate else ())
            state = jax.eval_shape(
                lambda: lm.init_cache(cfg, Bb, Sb, dtype=cfg.dtype))
            shaped = (self._shaped(self.params),
                      self._shaped(self.stacked_ctrl),
                      self._spec((Bb, 1)), self._shaped(state),
                      self._spec(()), self._spec(()))
        else:
            raise ValueError(f"unknown step kind {kind!r}")
        return jitted.lower(*shaped).compile()

    def _spec(self, shape, dtype=jnp.int32) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self.sharding)

    def _shaped(self, tree):
        return jax.tree.map(
            lambda a: self._spec(jnp.shape(a), jnp.result_type(a)), tree)


def stacked_controls(cfg: ArchConfig,
                     points: Sequence[ParetoPoint]) -> Dict[str, np.ndarray]:
    """The Pareto subnets' control tuples stacked along a leading axis:
    the executor's actuation input (indexed by a traced subnet id)."""
    ctrls = [sn.make_control(cfg, p.sub) for p in points]
    return {k: np.stack([np.asarray(c[k]) for c in ctrls]) for k in ctrls[0]}


def prefill_fn(cfg: ArchConfig, slice_mode: str = "mask") -> Callable:
    """The executor's prefill step, ``(params, stacked_ctrl, tokens (B,
    S), subnet_idx, lengths (B,)) -> (B, vocab)`` logits at each row's
    last real position; jit it (tests/test_tpu_compile.py compiles it
    for a described TPU). Its name names the compiled module in a
    device trace: ``jit_prefill_step``."""
    def prefill_step(params, stacked, tokens, idx, lengths):
        ctrl = {k: v[idx] for k, v in stacked.items()}
        logits = lm.forward(params, cfg, {"tokens": tokens}, ctrl,
                            slice_mode=slice_mode)
        # causal families: the pad never influences positions < length,
        # so gathering at length-1 IS the unpadded answer (pinned per
        # tier in tests/test_executor.py)
        pos = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
        return jnp.take_along_axis(logits, pos[:, None, None], axis=1)[:, 0]
    return prefill_step


def decode_fn(cfg: ArchConfig, slice_mode: str = "mask") -> Callable:
    """The executor's decode step, ``(params, stacked_ctrl, tokens (B,
    1), cache, subnet_idx, index) -> (logits, new_cache)``; compiled as
    ``jit_decode_step``."""
    def decode_step(params, stacked, tokens, cache, idx, index):
        ctrl = {k: v[idx] for k, v in stacked.items()}
        return lm.decode_step(params, cfg, tokens, ctrl, cache, index,
                              slice_mode=slice_mode)
    return decode_step


def init_params(cfg: ArchConfig, seed: int = 0) -> Dict:
    """Seeded supernet params for ``cfg``, initialized in one jitted
    program on the default device."""
    return jax.jit(lambda key: lm.init_model(key, cfg))(
        jax.random.PRNGKey(seed))


def build_executor(cfg: ArchConfig, seed: int = 0,
                   exec_cfg: Optional[ExecutorConfig] = None,
                   ) -> SubnetExecutor:
    """Init supernet params for ``cfg`` and wrap them in an executor on
    the first local device."""
    return SubnetExecutor(init_params(cfg, seed), cfg, exec_cfg=exec_cfg)


def build_replica_executors(cfg: ArchConfig, n_replicas: int, seed: int = 0,
                            exec_cfg: Optional[ExecutorConfig] = None,
                            ) -> List[SubnetExecutor]:
    """One executor per replica, replica ``r`` committed to
    ``jax.local_devices()[r % n_devices]`` (the ``launch/serve.py
    --execute real --replicas N`` path). Replicas that land on one
    device share its executor, so no device holds two weight copies."""
    params = init_params(cfg, seed)
    devices = jax.local_devices()
    by_device: Dict[Any, SubnetExecutor] = {}
    out = []
    for r in range(n_replicas):
        dev = devices[r % len(devices)]
        if dev not in by_device:
            by_device[dev] = SubnetExecutor(params, cfg, exec_cfg=exec_cfg,
                                            device=dev)
        out.append(by_device[dev])
    return out


def build_serving_executor(arch: str, seq_len: int = 16,
                           batches: Sequence[int] = (1, 2, 4, 8),
                           seed: int = 0,
                           reduced: bool = False) -> SubnetExecutor:
    """Registry-name entry point for serving children
    (``replica_proc --execute real``): build the supernet executor for
    ``arch`` at its published widths, or its ``reduced()`` twin when
    the caller asks for it, and AOT-warm the ``batches`` x ``seq_len``
    lattice so the first submit frame never pays an XLA compile. The
    coordinator must profile the same config for Pareto-set agreement."""
    from repro.configs import get_config
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    ex = build_executor(cfg, seed=seed)
    ex.warmup(batches=tuple(batches), seqs=(int(seq_len),))
    return ex
