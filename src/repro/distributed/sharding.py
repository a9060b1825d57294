"""ShardingPlan: one object mapping every tensor of an architecture —
parameters, batches, KV/SSM caches, control tuples — to a PartitionSpec
over the production mesh ``(pod, data, model)``.

Posture for 1000+ nodes: all placement is expressed as NamedSharding
rules keyed on tree paths + divisibility, so the same plan scales with
the mesh (a larger mesh only changes axis sizes). TP over ``model``
(attention heads / d_ff / vocab), EP over ``model`` for many-expert
MoE, DP/FSDP over ``(pod, data)``, and SP (sequence sharding) for
decode caches whose batch cannot cover the data axis.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
    return "/".join(out)


@dataclass
class ShardingPlan:
    # Mesh or AbstractMesh: rule evaluation only reads shape/axis_names,
    # so plans can be built (and unit-tested) without any devices.
    mesh: Any
    cfg: ArchConfig
    # 2D expert sharding (EP over model x FFN over data). Decode-only:
    # per-step activations are tiny, so the extra gather/reduce-scatter
    # over `data` costs ~MBs while resident expert bytes drop by the
    # data-axis size (llama4 decode: 45 GB -> 2.8 GB per device).
    # Train/prefill keep 1D EP — there the activation volume dominates.
    moe_2d: bool = False
    # FSDP / ZeRO-3: additionally shard parameters over the DP axes on
    # their first free divisible dimension; XLA all-gathers each scan
    # step's layer slice just-in-time (latency-hiding overlaps it with
    # the previous layer's compute). For models whose TP-sharded weights
    # alone exceed HBM (llama4 train: 46 GB/device).
    fsdp: bool = False

    @classmethod
    def abstract(cls, shape: Tuple[int, ...], axes: Tuple[str, ...],
                 cfg: ArchConfig, **kwargs) -> "ShardingPlan":
        """Plan over a device-free AbstractMesh (rule tests, planning
        tools on hosts without the target topology)."""
        from jax.sharding import AbstractMesh, AxisType
        mesh = AbstractMesh(tuple(shape), tuple(axes),
                            axis_types=(AxisType.Auto,) * len(axes))
        return cls(mesh, cfg, **kwargs)

    # ---- axis helpers -------------------------------------------------
    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def tp_axis(self) -> str:
        return "model"

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes]))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp_axis])

    def _dp_if(self, n: int):
        return self.dp_axes if n % max(self.dp_size, 1) == 0 else None

    def _tp_if(self, n: int):
        return self.tp_axis if n % max(self.tp_size, 1) == 0 else None

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ---- parameters ---------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """TP/EP rules keyed on the leaf name; stacked (scan) leading
        axes are never sharded."""
        name = path.rsplit("/", 1)[-1]
        rank = len(shape)

        def lead(base: Tuple) -> P:
            pad = rank - len(base)
            return P(*([None] * pad + list(base)))

        tp = self.tp_axis
        if name == "embed":
            return P(self._tp_if(shape[0]), None)
        if name == "head":
            return P(None, self._tp_if(shape[1]))
        if name in ("wq", "wk", "wv", "wu", "wg", "w_up", "w_in", "w_x", "swg", "swu"):
            if name in ("wg", "wu") and rank >= 3 and "moe" in path:
                # MoE experts (E, d, f): EP over model when E divides,
                # else TP on the expert FFN dim.
                E, _, f = shape[-3:]
                if E % self.tp_size == 0:
                    if self.moe_2d and f % max(self.dp_size, 1) == 0:
                        return lead((tp, None, self.dp_axes))
                    return lead((tp, None, None))
                return lead((None, None, self._tp_if(f)))
            return lead((None, self._tp_if(shape[-1])))
        if name in ("wo", "wd", "w_out", "w_down", "swd"):
            if name == "wd" and rank >= 3 and "moe" in path:
                E, f, _ = shape[-3:]
                if E % self.tp_size == 0:
                    if self.moe_2d and f % max(self.dp_size, 1) == 0:
                        return lead((tp, self.dp_axes, None))
                    return lead((tp, None, None))
                return lead((None, self._tp_if(f), None))
            return lead((self._tp_if(shape[-2]), None))
        # routers, biases, norm tables, SSM/conv small tensors: replicate
        return P(*([None] * rank))

    def _add_fsdp(self, spec: P, shape: Tuple[int, ...]) -> P:
        """Compose DP onto the first unsharded axis that divides."""
        if not self.fsdp:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        for i, (s, ax) in enumerate(zip(shape, entries)):
            if ax is None and s % max(self.dp_size, 1) == 0 and s >= self.dp_size:
                entries[i] = self.dp_axes
                return P(*entries)
        return spec

    def params(self, tree) -> Any:
        """Tree of NamedShardings matching ``tree`` (shapes or arrays)."""
        def one(path, leaf):
            spec = self.param_spec(_path_str(path), leaf.shape)
            return self.named(self._add_fsdp(spec, leaf.shape))
        return jax.tree_util.tree_map_with_path(one, tree)

    # ---- batches ------------------------------------------------------
    def batch_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        B = shape[0]
        dp = self._dp_if(B)
        rest = [None] * (len(shape) - 1)
        if name == "positions" and len(shape) == 3 and shape[0] == 3:
            # M-RoPE position streams: (3, B, S)
            return P(None, self._dp_if(shape[1]), None)
        return P(dp, *rest)

    def batch(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        return {k: self.named(self.batch_spec(k, v.shape)) for k, v in tree.items()}

    # ---- decode caches ------------------------------------------------
    def cache_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """Caches carry a leading stacked-layer axis (scan layout).

        Attention k/v: (L, B, Hkv, S, hd) — B over DP when divisible,
        else SP: S over DP (the long-context batch=1 case); heads over
        TP when divisible, else S additionally over TP.
        SSM/xLSTM states: (L, B, ...) — B over DP when divisible; the
        mamba head axis over TP when divisible.
        """
        name = path.rsplit("/", 1)[-1]
        if name in ("k", "v") and len(shape) in (4, 5):
            lead: Tuple = (None,) * (len(shape) - 4)
            B, H, S, hd = shape[-4:]
            b_ax = self._dp_if(B)
            h_ax = self._tp_if(H)
            # TP placement preference when heads don't divide: shard
            # head_dim, NOT sequence — a dynamic_update_slice at a
            # traced position on a sequence-sharded cache forces XLA to
            # all-gather the whole cache (temp = cache x tp; measured
            # 112 GB/device on llama4 decode_32k — see EXPERIMENTS.md
            # §Perf iteration 1).
            hd_ax = self._tp_if(hd) if h_ax is None else None
            s_axes = []
            if b_ax is None:
                s_axes.extend(self.dp_axes)
            if h_ax is None and hd_ax is None:
                s_axes.append(self.tp_axis)
            s_ax = tuple(s_axes) if s_axes and S % int(np.prod(
                [self.mesh.shape[a] for a in s_axes])) == 0 else None
            return P(*lead, b_ax, h_ax, s_ax, hd_ax)
        if name == "ssm" and len(shape) == 5:        # (L, B, H, N, Pdim)
            return P(None, self._dp_if(shape[1]), self._tp_if(shape[2]), None, None)
        if name == "conv" and len(shape) == 4:       # (L, B, W, C)
            return P(None, self._dp_if(shape[1]), None, self._tp_if(shape[3]))
        # xlstm states et al: (L, B, ...)
        if len(shape) >= 2:
            return P(None, self._dp_if(shape[1]), *([None] * (len(shape) - 2)))
        return P(*([None] * len(shape)))

    def cache(self, tree) -> Any:
        def one(path, leaf):
            return self.named(self.cache_spec(_path_str(path), leaf.shape))
        return jax.tree_util.tree_map_with_path(one, tree)

    # ---- control tuple / scalars --------------------------------------
    def replicated(self, tree) -> Any:
        return jax.tree.map(
            lambda leaf: self.named(P(*([None] * getattr(leaf, "ndim", len(leaf.shape))))),
            tree)
