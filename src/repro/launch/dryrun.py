import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
# The lines above MUST run before any jax import: jax fixes its platform and
# device count at first init.  The dry-run lowers for 512 host devices and
# never takes an accelerator, even on a machine that has one.

# Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.
#
# For each cell this lowers the real step function (train_step for train_4k,
# prefill_step for prefill_32k, serve_step for decode shapes) against
# ShapeDtypeStruct inputs with full production shardings, compiles it, prints
# memory_analysis/cost_analysis, parses the post-SPMD HLO for collective
# traffic, and appends a JSON record to the manifest.  Failures here
# (sharding mismatch, OOM at compile, unsupported collective) are bugs.
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
#   python -m repro.launch.dryrun --arch all --shape all [--multi-pod]

import argparse
import json
import math
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES_BY_NAME, InputShape, cell_is_runnable
from repro.configs.registry import ARCH_IDS, get_config
from repro.dist.sharding import default_rules, logical_sharding, spec_for, tree_shardings
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import RooflineTerms, collective_stats, model_flops_for
from repro.models.registry import (
    decode_cache_len,
    make_serve_step,
    make_train_step,
    model_fns,
    shapes_and_axes,
)
from repro.optim.optimizers import opt_state_axes

_BATCH_AXES: Dict[str, tuple] = {
    "tokens": ("act_batch", None),
    "frames": ("act_batch", None, None),
    "patch_embeds": ("act_batch", None, None),
    "token": ("act_batch",),
    "pos": (),
}


def _batch_shardings(specs: Dict[str, Any], mesh, rules):
    from jax.sharding import NamedSharding

    return {
        k: NamedSharding(mesh, spec_for(_BATCH_AXES[k], rules)) for k in specs
    }


def _lower_and_compile(cfg, shape: InputShape, mesh, rules, *, compile_cell=True,
                       verbose=False) -> Dict[str, Any]:
    """Lower + compile one step function; return costs + memory stats."""
    fns = model_fns(cfg)
    out: Dict[str, Any] = {}
    t0 = time.time()
    with mesh, logical_sharding(mesh, rules):
        key = jax.random.PRNGKey(0)
        params_shapes, params_axes = shapes_and_axes(fns.init, key)
        params_sh = tree_shardings(params_axes, mesh, rules)
        specs = fns.input_specs(shape)
        batch_sh = _batch_shardings(specs, mesh, rules)

        if shape.kind == "train":
            train_step, opt = make_train_step(cfg)
            opt_shapes = jax.eval_shape(opt.init, params_shapes)
            opt_axes = opt_state_axes(cfg.optimizer, params_axes, params_shapes)
            opt_sh = tree_shardings(opt_axes, mesh, rules)
            jitted = jax.jit(
                train_step,
                in_shardings=(params_sh, opt_sh, batch_sh),
                out_shardings=(params_sh, opt_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_shapes, opt_shapes, specs)
        elif shape.kind == "prefill":
            prefill_step = lambda p, b: fns.prefill(p, b)
            jitted = jax.jit(prefill_step, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_shapes, specs)
        else:  # decode
            serve_step = make_serve_step(cfg)
            cache_shapes, cache_axes = shapes_and_axes(
                lambda: fns.make_cache(shape.global_batch, decode_cache_len(shape.seq_len))
            )
            cache_sh = tree_shardings(cache_axes, mesh, rules)
            jitted = jax.jit(
                serve_step,
                in_shardings=(params_sh, cache_sh, batch_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_shapes, cache_shapes, specs)

        out["lower_s"] = round(time.time() - t0, 2)
        if not compile_cell:
            out["status"] = "lowered"
            return out

        t1 = time.time()
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        if verbose:
            print(mem)  # proves it fits
        if mem is not None:
            for attr in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
            ):
                v = getattr(mem, attr, None)
                if v is not None:
                    out[attr] = int(v)
            out["bytes_per_device"] = int(
                out.get("argument_size_in_bytes", 0) + out.get("temp_size_in_bytes", 0)
            )

        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["flops"] = float((ca or {}).get("flops", 0.0))
        out["hbm_bytes"] = float((ca or {}).get("bytes accessed", 0.0))
        coll = collective_stats(compiled.as_text())
        out["wire_bytes"] = coll.wire_bytes
        out["collectives"] = coll.to_dict()
        out["status"] = "ok"
    return out


def _scaled(cfg, repeats, n_enc: Optional[int] = None, shape: Optional[InputShape] = None):
    """Depth-scaled, scan-free variant for cost probes.

    Every lax.scan in the step is removed (layers unrolled, attention chunk =
    full sequence, unchunked loss, no remat) because XLA's cost analysis
    counts a loop body once.  FLOPs become exact; HLO bytes reflect unfused
    oracle attention (upper bound — the Pallas flash kernel removes the S²
    traffic on real TPUs; see EXPERIMENTS.md §Roofline notes).
    """
    from repro.configs.base import LayerGroup

    groups = tuple(
        LayerGroup(g.pattern, r) for g, r in zip(cfg.groups, repeats)
    )
    kw: Dict[str, Any] = {
        "groups": groups,
        "scan_layers": False,
        "remat": "none",
        "loss_chunk": 0,
    }
    if shape is not None:
        kw["attn_chunk"] = max(shape.seq_len, cfg.attn_chunk)
    if n_enc is not None:
        kw["n_enc_layers"] = n_enc
    return cfg.replace(**kw)


def exact_costs(cfg, shape, mesh, rules) -> Dict[str, float]:
    """Exact HLO costs via depth extrapolation.

    Compile scan-free 1×/2× depth probes: per-group cost = f(group@2) −
    f(base); total = f(base) + Σ_g (R_g − 1)·per_g (+ encoder analog).
    Exact for homogeneous stacks (every repeat of a group pattern is
    identical compute).
    """
    base_repeats = [1] * len(cfg.groups)
    enc_base = 1 if cfg.is_encdec else None
    keys = ("flops", "hbm_bytes", "wire_bytes")

    def costs(c) -> Dict[str, float]:
        r = _lower_and_compile(c, shape, mesh, rules)
        return {k: r[k] for k in keys}

    base = costs(_scaled(cfg, base_repeats, enc_base, shape))
    total = dict(base)
    for gi, group in enumerate(cfg.groups):
        if group.repeat == 1:
            continue
        reps = list(base_repeats)
        reps[gi] = 2
        probe = costs(_scaled(cfg, reps, enc_base, shape))
        for k in keys:
            total[k] += (group.repeat - 1) * (probe[k] - base[k])
    if cfg.is_encdec and cfg.n_enc_layers > 1:
        probe = costs(_scaled(cfg, base_repeats, 2, shape))
        for k in keys:
            total[k] += (cfg.n_enc_layers - 1) * (probe[k] - base[k])
    return total


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    compile_cell: bool = True,
    verbose: bool = True,
    exact: bool = False,
    overrides: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = default_rules(cfg, mesh, shape)

    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count() if cfg.n_experts else cfg.param_count(),
    }

    runnable, reason = cell_is_runnable(arch, shape_name)
    if not runnable:
        record["status"] = "skipped"
        record["reason"] = reason
        return record

    # 1) full-depth scanned compile: the runnability/memory proof
    full = _lower_and_compile(
        cfg, shape, mesh, rules, compile_cell=compile_cell, verbose=verbose
    )
    record.update(full)
    if not compile_cell:
        return record

    # 2) exact roofline costs via unrolled depth probes
    flops, hbm, wire = full["flops"], full["hbm_bytes"], full["wire_bytes"]
    if exact:
        ex = exact_costs(cfg, shape, mesh, rules)
        flops, hbm, wire = ex["flops"], ex["hbm_bytes"], ex["wire_bytes"]
        record["exact"] = True

    terms = RooflineTerms(
        flops=flops,
        hbm_bytes=hbm,
        wire_bytes=wire,
        chips=chips,
        model_flops=model_flops_for(cfg, shape),
    )
    record.update(terms.to_dict())
    return record


def _cell_cost_proxy(arch: str, shape_name: str) -> float:
    """Static cheapness proxy for a cell — parameter bytes × tokens — so
    the compile-gate CI job can pick the N cheapest cells without
    compiling anything (eval_shape only, no device execution)."""
    cfg = get_config(arch)
    fns = model_fns(cfg)
    params_shapes, _axes = shapes_and_axes(fns.init, jax.random.PRNGKey(0))
    param_bytes = sum(
        math.prod(l.shape) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params_shapes)
    )
    shape = SHAPES_BY_NAME[shape_name]
    return float(param_bytes) * float(shape.seq_len * shape.global_batch)


def _cheapest_cells(n: int, archs, shapes, meshes):
    """The n cheapest *runnable* (arch, shape) cells by the static proxy,
    each run on every requested mesh."""
    costed = []
    for arch in archs:
        for shape in shapes:
            runnable, _reason = cell_is_runnable(arch, shape)
            if not runnable:
                continue
            try:
                costed.append((_cell_cost_proxy(arch, shape), arch, shape))
            except Exception:
                continue  # un-costable cell: let the full sweep report it
    costed.sort(key=lambda t: t[0])
    return [(arch, shape, mp) for _c, arch, shape in costed[:n] for mp in meshes]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    compile_group = ap.add_mutually_exclusive_group()
    compile_group.add_argument("--compile", action="store_true",
                               dest="force_compile",
                               help="full compile of each cell (the default; "
                                    "explicit flag for the compile-gate CI "
                                    "job, mutually exclusive with "
                                    "--no-compile)")
    compile_group.add_argument("--no-compile", action="store_true")
    ap.add_argument("--cheapest", type=int, default=None, metavar="N",
                    help="only the N cheapest runnable cells (static "
                         "param-bytes x tokens proxy) — the nightly "
                         "compile-gate subset")
    ap.add_argument("--exact", action="store_true",
                    help="add unrolled depth probes for exact HLO cost analysis")
    ap.add_argument("--shard", default=None, metavar="K/N",
                    help="run only the K-th of N round-robin shards of the "
                         "cell list (1-based), so a CI matrix can fan the "
                         "sweep across parallel jobs; composes with "
                         "--cheapest (shards the cheapest-N subset)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES_BY_NAME) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    if args.cheapest is not None:
        cells = _cheapest_cells(args.cheapest, archs, shapes, meshes)
        print(f"compile-gate subset: {len(cells)} cheapest cells "
              f"(of {len(archs) * len(shapes) * len(meshes)} requested)",
              flush=True)
    else:
        cells = [(arch, shape, mp) for arch in archs for shape in shapes
                 for mp in meshes]

    if args.shard:
        try:
            k, n = (int(x) for x in args.shard.split("/"))
        except ValueError:
            raise SystemExit(f"bad --shard {args.shard!r}: want K/N")
        if not 1 <= k <= n:
            raise SystemExit(f"bad --shard {args.shard!r}: want 1 <= K <= N")
        cells = cells[k - 1::n]  # round-robin keeps shards cost-balanced
        print(f"shard {k}/{n}: {len(cells)} cells", flush=True)

    n_fail = 0
    for arch, shape, mp in cells:
        tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
        print(f"=== {tag} ===", flush=True)
        try:
            rec = lower_cell(
                arch, shape, multi_pod=mp,
                compile_cell=args.force_compile or not args.no_compile,
                exact=args.exact,
                verbose=False,
            )
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            rec = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
            }
            n_fail += 1
        print(json.dumps({k: rec.get(k) for k in (
            "status", "bottleneck", "t_compute_s", "t_memory_s",
            "t_collective_s", "bytes_per_device", "compile_s", "reason", "error",
        )}), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
