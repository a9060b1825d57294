"""Multi-pod dry-run: lower + compile every (architecture x input
shape) cell on the production meshes with ShapeDtypeStruct inputs (no
allocation), then extract memory_analysis / cost_analysis / collective
bytes for the roofline report.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Results land in results/dryrun/<arch>__<shape>__<mesh>.json; failures
are sharding bugs by definition and fail loudly. ``main()`` asks the
CPU backend for 512 fake devices before JAX first touches one.
"""
import argparse
import json
import os
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, assigned_archs, shape_applicable
from repro.core import subnet as sn
from repro.distributed.sharding import ShardingPlan
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.roofline import hlo as hlo_mod
from repro.roofline.report import RooflineTerms

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _cache_constraints(plan, cfg, cache_tree):
    """Per-stage, per-layer NamedShardings for the decode scan body
    (strip the leading stacked-layer axis from the plan's cache specs)."""
    from jax.sharding import PartitionSpec as P
    out = []
    for si, stage_cache in enumerate(cache_tree["stages"]):
        def one(path, leaf, si=si):
            from repro.distributed.sharding import _path_str
            spec = plan.cache_spec(_path_str(path), leaf.shape)
            return plan.named(P(*spec[1:]))     # drop stacked-layer axis
        out.append(jax.tree_util.tree_map_with_path(one, stage_cache))
    return out


def _step_fn(cfg, kind: str, moe_groups: int, *, slice_mode: str = "mask",
             remat: bool = False, cache_constraints=None, moe_group_axes=None,
             microbatch: int = 0, grad_shardings=None):
    if kind == "train":
        def train_step(params, batch, ctrl):
            def loss(p, b):
                return lm.loss_fn(p, cfg, b, ctrl, slice_mode=slice_mode,
                                  remat=remat, moe_groups=moe_groups,
                                  moe_group_axes=moe_group_axes)

            def shard_grads(g):
                # ZeRO-2: reduce-scatter gradients over DP — without it
                # every device holds the full fp32 grad/accumulator tree
                # (measured 96 GB/device on qwen2.5-14b train_4k)
                if grad_shardings is None:
                    return g
                return jax.tree.map(jax.lax.with_sharding_constraint,
                                    g, grad_shardings)

            if microbatch:
                n = microbatch

                def split(x):
                    return x.reshape((n, x.shape[0] // n) + x.shape[1:])

                mb = jax.tree.map(split, batch)

                def acc(carry, mb_i):
                    l_acc, g_acc = carry
                    l, g = jax.value_and_grad(loss)(params, mb_i)
                    g = shard_grads(g)
                    return (l_acc + l / n,
                            jax.tree.map(lambda a, b2: a + b2 / n, g_acc, g)), None

                zeros = shard_grads(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params))
                (l, grads), _ = jax.lax.scan(acc, (0.0, zeros), mb)
            else:
                l, grads = jax.value_and_grad(loss)(params, batch)
                grads = shard_grads(grads)
            # SGD-flavored apply keeps the dry-run optimizer-shape-true
            # without doubling memory vs AdamW moments (reported
            # separately in EXPERIMENTS.md).
            new_params = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32) - 1e-3 * g).astype(p.dtype),
                params, grads)
            return l, new_params
        return train_step
    if kind == "prefill":
        def prefill_step(params, batch, ctrl):
            return lm.prefill(params, cfg, batch, ctrl, slice_mode=slice_mode,
                              moe_groups=moe_groups,
                              moe_group_axes=moe_group_axes)
        return prefill_step

    if kind == "decode_int8":
        from repro.serving import quantize as QZ

        def serve_step_q(q_params, scales, tokens, ctrl, cache, index):
            params = QZ.dequantize_tree(q_params, scales)
            return lm.decode_step(params, cfg, tokens, ctrl, cache, index,
                                  slice_mode=slice_mode,
                                  cache_constraints=cache_constraints)
        return serve_step_q

    def serve_step(params, tokens, ctrl, cache, index):
        return lm.decode_step(params, cfg, tokens, ctrl, cache, index,
                              slice_mode=slice_mode,
                              cache_constraints=cache_constraints)
    return serve_step


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             save: bool = True, remat: bool = False,
             microbatch: int = 0, int8_weights: bool = False,
             fsdp: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "reason": why}
        if save:
            _save(rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    plan = ShardingPlan(mesh, cfg, moe_2d=(shape.kind == "decode"),
                        fsdp=fsdp)
    sp = S.input_specs(cfg, shape)
    sh = S.input_shardings(plan, cfg, shape, sp)
    constraints = (_cache_constraints(plan, cfg, sp["cache"])
                   if shape.kind == "decode" else None)
    grad_sh = None
    if shape.kind == "train":
        from repro.training import optimizer as _opt
        grad_sh = _opt.state_shardings(plan, sp["params"])["m"]
    kind = shape.kind
    if int8_weights and kind == "decode":
        kind = "decode_int8"
    step = _step_fn(cfg, kind, moe_groups=plan.dp_size, remat=remat,
                    cache_constraints=constraints,
                    moe_group_axes=plan.dp_axes, microbatch=microbatch,
                    grad_shardings=grad_sh)

    t0 = time.time()
    with mesh:
        if shape.kind in ("train", "prefill"):
            jitted = jax.jit(step, in_shardings=(sh["params"], sh["batch"],
                                                 sh["ctrl"]))
            lowered = jitted.lower(sp["params"], sp["batch"], sp["ctrl"])
        else:
            # pin the output cache to the input layout: donation can
            # only alias when shardings match, otherwise XLA
            # materializes a full re-laid-out cache in temp space
            logits_sh = plan.named(jax.sharding.PartitionSpec(
                plan.dp_axes if shape.global_batch % plan.dp_size == 0
                else None, None, None))
            if int8_weights:
                from repro.serving import quantize as QZ
                q_sp, sc_sp = QZ.quantize_specs(sp["params"])
                sc_sh = plan.replicated(sc_sp)
                jitted = jax.jit(step, in_shardings=(sh["params"], sc_sh,
                                                     sh["tokens"], sh["ctrl"],
                                                     sh["cache"], sh["index"]),
                                 out_shardings=(logits_sh, sh["cache"]),
                                 donate_argnums=(4,))
                lowered = jitted.lower(q_sp, sc_sp, sp["tokens"], sp["ctrl"],
                                       sp["cache"], sp["index"])
            else:
                jitted = jax.jit(step, in_shardings=(sh["params"], sh["tokens"],
                                                     sh["ctrl"], sh["cache"],
                                                     sh["index"]),
                                 out_shardings=(logits_sh, sh["cache"]),
                                 donate_argnums=(3,))
                lowered = jitted.lower(sp["params"], sp["tokens"], sp["ctrl"],
                                       sp["cache"], sp["index"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll_bytes, breakdown = hlo_mod.collective_bytes(text)
    counts = hlo_mod.collective_count(text)
    f32_copy_bytes = _cpu_f32_weight_copies(plan, sp["params"], text)

    terms = RooflineTerms(
        arch=arch, shape=shape_name, mesh=mesh_kind,
        chips=mesh.devices.size,
        hlo_flops_per_device=float(ca.get("flops", 0.0)),
        hlo_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        collective_bytes_per_device=coll_bytes,
        model_flops_total=S.model_flops(cfg, shape),
        argument_bytes_per_device=float(ma.argument_size_in_bytes),
        temp_bytes_per_device=float(ma.temp_size_in_bytes),
        collective_breakdown=breakdown,
    )
    from repro.kernels.dispatch import model_tier
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "status": "ok", "kernel_tier": model_tier(),
           "remat": remat, "microbatch": microbatch,
           "int8_weights": int8_weights, "fsdp": fsdp,
           "lower_s": round(t_lower, 1),
           "compile_s": round(t_compile, 1),
           "collective_counts": counts,
           "output_bytes_per_device": float(ma.output_size_in_bytes),
           # CPU-backend artifact: bf16 dots are promoted to f32, so the
           # compiler materializes f32 copies of bf16 weights that a TPU
           # (native-bf16 MXU) never allocates. Subtract for the
           # TPU-projected temp footprint.
           "cpu_f32_weight_copy_bytes": f32_copy_bytes,
           "temp_bytes_tpu_projected": float(ma.temp_size_in_bytes) - f32_copy_bytes,
           **terms.to_dict()}
    if save:
        _save(rec)
    return rec


def _cpu_f32_weight_copies(plan, param_specs, hlo_text: str) -> float:
    """Bytes of f32 copies of bf16 param leaves present in the HLO
    (each distinct local weight shape counted once — buffer assignment
    reuses allocations across layers of equal shape)."""
    import re
    import numpy as np
    from repro.distributed.sharding import _path_str
    import jax as _jax

    local_shapes = set()
    for path, leaf in _jax.tree_util.tree_leaves_with_path(param_specs):
        if leaf.dtype != jnp.bfloat16:
            continue
        spec = plan.param_spec(_path_str(path), leaf.shape)
        dims = []
        for size, ax in zip(leaf.shape, tuple(spec) + (None,) * len(leaf.shape)):
            n = 1
            if ax is not None:
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    n *= plan.mesh.shape[a]
            dims.append(size // n)
        if np.prod(dims) * 4 > 64 * 2**20:     # only copies that matter
            local_shapes.add(tuple(dims))
    total = 0.0
    for dims in local_shapes:
        pat = r"f32\[" + ",".join(str(d) for d in dims) + r"\]"
        if re.search(pat, hlo_text):
            total += float(np.prod(dims)) * 4
    return total


def _save(rec: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--int8-weights", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    args = ap.parse_args()

    archs = assigned_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                out = os.path.join(
                    RESULTS_DIR, f"{arch}__{shape}__{mesh_kind}.json")
                if args.skip_done and os.path.exists(out):
                    continue
                tag = f"{arch} x {shape} x {mesh_kind}"
                try:
                    rec = run_cell(arch, shape, mesh_kind, remat=args.remat,
                                   microbatch=args.microbatch,
                                   int8_weights=args.int8_weights,
                                   fsdp=args.fsdp)
                    if rec["status"] == "skipped":
                        print(f"[skip] {tag}: {rec['reason']}", flush=True)
                    else:
                        print(f"[ ok ] {tag}: dominant={rec['dominant']} "
                              f"frac={rec['roofline_fraction']:.3f} "
                              f"compile={rec['compile_s']}s", flush=True)
                except Exception as e:  # noqa: BLE001 - report and continue
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: "
                         + "; ".join(t for t, _ in failures))


if __name__ == "__main__":
    main()
