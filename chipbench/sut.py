"""The system under test, built as ``launch/serve.py --execute real``
builds it, around the benchmark's own weights and a thin wrapper of
``SubnetExecutor.run_prefill`` that records the benchmark's spans.

From the program the benchmark takes the executor, the measured
profile, SlackFit, the Router (``ClusterRouter`` over one-chip
replicas where the traffic file asks for ``replicas``), the engine's
dispatch records and its compile counter. Everything else (weights,
traffic, outcomes, the reference) is the benchmark's.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List

from chipbench import weights as bench_weights


def program_config(cfg: Dict[str, Any]):
    """The program's ``ArchConfig`` for the configuration file: the
    registry entry's structure at the file's numbers."""
    from repro.configs import get_config
    from repro.configs.base import ElasticSpec, Stage

    base = get_config(cfg["registry"])
    shape = (tuple(s.pattern for s in base.stages), base.norm, base.ffn_act,
             base.pos_embed, base.frontend, base.n_experts)
    if shape != ((("attn", "mlp"),), "rmsnorm", "swiglu", "rope", "token", 0):
        raise ValueError(f"{cfg['registry']}: the benchmark serves dense "
                         f"attention + SwiGLU stacks, not {shape}")
    e = cfg["elastic"]
    return base.replace(
        stages=(Stage(("attn", "mlp"), repeat=cfg["num_hidden_layers"]),),
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        sliding_window=cfg["sliding_window"] or 0, dtype=cfg["torch_dtype"],
        elastic=ElasticSpec(depth_fracs=tuple(e["depth_fracs"]),
                            ffn_fracs=tuple(e["ffn_fracs"]),
                            head_fracs=tuple(e["head_fracs"])))


@dataclass
class Call:
    """One executor call, as the benchmark's wrapper saw it."""

    t0: float                 # perf_counter s
    t1: float
    replica: int
    subnet: int
    rows: int                 # real rows (the engine's batch)


@dataclass
class System:
    cfg: Any                              # program ArchConfig
    executors: List[Any]
    weights: List[Any]                    # per replica, the benchmark's
    profile: Any
    raw_profile: Any
    policy: Any
    workers: List[Any]                    # per replica
    calls: List[Call] = field(default_factory=list)
    setup_notes: Dict[str, Any] = field(default_factory=dict)

    def make_router(self, slo_s: float, seed: int):
        from repro.serving import runtime
        if len(self.workers) > 1:
            return runtime.ClusterRouter(self.profile, self.policy,
                                         self.workers, placement_seed=seed,
                                         slo=slo_s)
        return runtime.Router(self.profile, self.policy, self.workers[0],
                              executor=self.executors[0])

    def subnet_of(self, pareto_idx: int) -> Dict[str, float]:
        sub = self.executors[0].points[pareto_idx].sub
        return {"depth_frac": sub.depth_frac, "ffn_frac": sub.ffn_frac,
                "head_frac": sub.head_frac, "subnet_id": sub.subnet_id}

    def free(self) -> None:
        """Drop the program's state (executors, compiled programs,
        routers' workers); the benchmark's weights stay."""
        self.executors.clear()
        self.workers.clear()
        self.policy = self.profile = self.raw_profile = None


def build(cfg_file: Dict[str, Any], traffic: Dict[str, Any], seed: int,
          devices, span: Callable) -> System:
    """Weights from the seed on each replica's chip, executors AOT-warmed
    on the traffic's buckets only, the launcher's measured profile and
    SlackFit with the traffic's workers per replica."""
    from repro.serving import policies, profiler
    from repro.serving.executor import SubnetExecutor
    from repro.serving.runtime import make_supernet_workers

    cfg = program_config(cfg_file)
    n_rep = int(traffic.get("replicas", 1))
    devs = list(devices)[:n_rep]
    notes: Dict[str, Any] = {}
    t = time.perf_counter()
    ws = [bench_weights.make(cfg_file, seed, d) for d in devs]
    import jax
    jax.block_until_ready(ws)
    notes["weights_s"] = time.perf_counter() - t
    exs = [SubnetExecutor(w, cfg, device=d) for w, d in zip(ws, devs)]
    batches = tuple(traffic["batches"])
    seq = int(traffic["prompt_len"])
    notes["warmup"] = [ex.warmup(batches=batches, seqs=(seq,)) for ex in exs]
    t = time.perf_counter()
    raw = exs[0].measured_profile(batches=batches, seq_len=seq,
                                  monotonize=False)
    notes["profile_s"] = time.perf_counter() - t
    prof = profiler.monotonized(raw)
    system = System(cfg=cfg, executors=exs, weights=ws, profile=prof,
                    raw_profile=raw, policy=policies.ALL_POLICIES["slackfit"](),
                    workers=[], setup_notes=notes)

    def wrap(r: int, ex) -> Callable:
        def run(subnet_idx, batch):
            t0 = time.perf_counter()
            with span("executor_call"):
                out = ex.run_prefill(subnet_idx, batch)
            system.calls.append(Call(t0, time.perf_counter(), r,
                                     int(subnet_idx), len(batch)))
            return out
        return run

    system.workers = [make_supernet_workers(int(traffic["workers"]),
                                            wrap(r, ex), ex.pad_batch)
                      for r, ex in enumerate(exs)]
    return system


def no_span(_name: str):
    return contextlib.nullcontext()
