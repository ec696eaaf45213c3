import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, with ShapeDtypeStruct inputs (no allocation).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_0_6b \
        --shape train_4k [--multi-pod] [--feddif]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out benchmarks/results

Writes one JSON per (arch, shape, mesh) with memory analysis, cost analysis,
and the per-collective byte breakdown parsed from the partitioned HLO —
the §Roofline inputs.

MUST be run as its own process: the XLA_FLAGS line above executes before any
jax import (jax locks the device count on first init).
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, SHAPES, ModelConfig, ShapeConfig, get_config
from repro.distributed import sharding as sh
from repro.distributed.fedshard import make_diffusion_step
from repro.launch.mesh import make_production_mesh
from repro.models.zoo import build_model
from repro.train import optimizer as opt_lib
from repro.train.trainstep import (TrainState, make_serve_step,
                                   make_train_step)

COLLECTIVE_RE = re.compile(
    r"(\(|= )((?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?(?:, )?)+)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}


def _bytes_of_shape_str(s: str) -> float:
    total = 0.0
    for dt, dims in SHAPE_RE.findall(s):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective op in partitioned HLO."""
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start)?\(", line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        # `%op.N = <shape(s)> all-gather(...)` — output shape(s) sit between
        # the `=` and the op name.  Skip the paired `-done` ops (same shape).
        if re.search(r"-done\(", line):
            continue
        rhs = line.split("=", 1)[1]
        rhs = rhs.split(m.group(1))[0]
        b = _bytes_of_shape_str(rhs)
        out[kind] = out.get(kind, 0.0) + b
        counts[kind] = counts.get(kind, 0) + 1
    out["_counts"] = counts
    return out


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input (weak-type-correct,
    shardable, no device allocation)."""
    model = build_model(cfg)
    return model.input_specs(shape)


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              feddif: bool = False, fsdp: bool | None = None,
              donate: bool = True, accum: int = 0) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return {"status": "skipped",
                "reason": "full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md §4)"}
    if accum == 0:
        # auto: big archs accumulate gradients over microbatches so live
        # activations fit the 16 GB/chip HBM budget (§Perf).
        n = cfg.param_count()
        accum = 8 if n > 5e10 else 4 if n > 5e9 else 1
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    opt = opt_lib.sgd(momentum=0.9)
    batch = input_specs(cfg, shape)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.mode == "train":
            key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
            state_shapes = jax.eval_shape(
                lambda k: TrainState(
                    params=model.init(k),
                    opt_state=opt.init(model.init(k)),
                    step=jnp.zeros((), jnp.int32)),
                key_spec)
            pspecs = sh.param_specs(state_shapes.params, cfg, mesh, fsdp)
            sspecs = sh.state_specs(pspecs, state_shapes.opt_state)
            bspecs = sh.batch_specs(batch, shape, mesh)
            from repro.models.layers import perf_opt_enabled
            accum_eff = accum if perf_opt_enabled("grad_accum") else 1
            if accum_eff > 1:
                # microbatch-stacked inputs: (K, B/K, ...) — the K axis is
                # replicated, B/K stays sharded over the data axes
                batch = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        (accum_eff, x.shape[0] // accum_eff) + x.shape[1:],
                        x.dtype), batch)
                bspecs = jax.tree.map(
                    lambda s: type(s)(None, *tuple(s)), bspecs,
                    is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))
            step_fn = make_train_step(model, opt, opt_lib.constant_lr(0.01),
                                      accum_steps=accum_eff)
            jitted = jax.jit(
                step_fn,
                in_shardings=(sh.named(mesh, sspecs),
                              sh.named(mesh, bspecs)),
                donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_shapes, batch)
        else:
            pspecs_shapes = jax.eval_shape(model.init,
                                           jax.ShapeDtypeStruct((2,),
                                                                jnp.uint32))
            pspecs = sh.param_specs(pspecs_shapes, cfg, mesh, fsdp)
            if shape.mode == "prefill":
                from repro.train.trainstep import make_prefill_step
                step_fn = make_prefill_step(model)
                bspecs = sh.batch_specs(batch, shape, mesh)
                jitted = jax.jit(step_fn,
                                 in_shardings=(sh.named(mesh, pspecs),
                                               sh.named(mesh, bspecs)))
                lowered = jitted.lower(pspecs_shapes, batch)
            else:  # decode
                cache = model.cache_specs(shape)
                cspecs = sh.cache_specs(cache, shape, mesh)
                bspecs = sh.batch_specs(batch, shape, mesh)
                step_fn = make_serve_step(model)
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(sh.named(mesh, pspecs),
                                  sh.named(mesh, bspecs["tokens"]),
                                  sh.named(mesh, cspecs), None),
                    donate_argnums=(2,) if donate else ())
                pos = jax.ShapeDtypeStruct((), jnp.int32)
                lowered = jitted.lower(pspecs_shapes, batch["tokens"],
                                       cache, pos)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    from repro.launch.hlo_analysis import xla_cost_analysis
    cost = xla_cost_analysis(compiled)
    txt = compiled.as_text()
    dump = os.environ.get("DRYRUN_DUMP_HLO")
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(
                dump, f"{arch}_{shape_name}_"
                f"{'512' if multi_pod else '256'}.hlo"), "w") as f:
            f.write(txt)
    coll = collective_bytes(txt)
    # Trip-count-aware accounting (XLA's cost_analysis counts while bodies
    # once; scan-over-layers models need the corrected numbers).
    from repro.launch.hlo_analysis import analyze_hlo
    hlo = analyze_hlo(txt)
    result = {
        "status": "ok",
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        "hlo_dot_flops_per_device": hlo["dot_flops"],
        "hlo_hbm_bytes_per_device": hlo["hbm_bytes"],
        "hlo_collective_bytes_per_device": hlo["collective_bytes"],
        "hlo_collective_counts": hlo["collective_counts"],
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "collectives": coll,
        "param_count": get_config(arch).param_count(),
        "active_param_count": get_config(arch).active_param_count(),
        "accum_steps": accum if shape.mode == "train" else None,
    }
    return result


def feddif_lower(arch: str, fsdp: bool | None = None) -> dict:
    """Lower the client-per-pod FedDif diffusion step on the 2×16×16 mesh.

    Proves the paper's data plane (D2D ppermute + weighted aggregation)
    shards over the ``pod`` axis.  Uses train_4k per-client shapes.
    """
    from jax.sharding import PartitionSpec as P
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    mesh = make_production_mesh(multi_pod=True)
    model = build_model(cfg)
    opt = opt_lib.sgd(momentum=0.9)
    npod = mesh.shape["pod"]
    t0 = time.time()

    with jax.set_mesh(mesh):
        key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        base_state = jax.eval_shape(
            lambda k: TrainState(params=model.init(k),
                                 opt_state=opt.init(model.init(k)),
                                 step=jnp.zeros((), jnp.int32)), key_spec)
        # stack a leading client axis (one client per pod)
        state_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((npod,) + x.shape, x.dtype),
            base_state)
        base_pspecs = sh.param_specs(base_state.params, cfg, mesh, fsdp)
        stackP = lambda t: jax.tree.map(lambda s: P("pod", *s), t,
                                        is_leaf=lambda x: isinstance(x, P))
        pspecs = stackP(base_pspecs)
        sspecs = sh.state_specs(pspecs, state_shapes.opt_state)
        sspecs = TrainState(params=pspecs,
                            opt_state=sspecs.opt_state, step=P("pod"))

        batch = model.input_specs(shape)
        batch = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (npod, x.shape[0] // npod) + x.shape[1:], x.dtype), batch)
        bspecs = jax.tree.map(
            lambda x: P("pod", "data", *([None] * (len(x.shape) - 2))), batch)

        step_fn = make_diffusion_step(model, opt)
        jitted = jax.jit(
            step_fn,
            in_shardings=(sh.named(mesh, sspecs), sh.named(mesh, bspecs),
                          None, None, None),
            donate_argnums=(0,))
        perm = jax.ShapeDtypeStruct((npod,), jnp.int32)
        mask = jax.ShapeDtypeStruct((npod,), jnp.bool_)
        w = jax.ShapeDtypeStruct((npod,), jnp.float32)
        lowered = jitted.lower(state_shapes, batch, perm, mask, w)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    from repro.launch.hlo_analysis import analyze_hlo, xla_cost_analysis
    cost = xla_cost_analysis(compiled)
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    hlo = analyze_hlo(txt)
    return {"status": "ok", "arch": arch, "shape": "train_4k",
            "mesh": "2x16x16-feddif", "chips": 512,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
            "hlo_dot_flops_per_device": hlo["dot_flops"],
            "hlo_hbm_bytes_per_device": hlo["hbm_bytes"],
            "hlo_collective_bytes_per_device": hlo["collective_bytes"],
            "hlo_collective_counts": hlo["collective_counts"],
            "collectives": coll}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--feddif", action="store_true",
                    help="lower the client-per-pod FedDif diffusion step")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fsdp", choices=["auto", "on", "off"], default="auto")
    ap.add_argument("--out", default=None, help="JSON output directory")
    args = ap.parse_args()
    fsdp = {"auto": None, "on": True, "off": False}[args.fsdp]

    jobs = []
    if args.all:
        for arch in ARCH_IDS:
            for shp in SHAPES:
                jobs.append((arch, shp, args.multi_pod))
    else:
        jobs.append((args.arch, args.shape, args.multi_pod))

    results = []
    for arch, shp, mp in jobs:
        label = f"{arch}/{shp}/{'512' if mp else '256'}"
        try:
            if args.feddif:
                r = feddif_lower(arch, fsdp)
            else:
                r = lower_one(arch, shp, mp, fsdp=fsdp)
        except Exception as e:
            r = {"status": "error", "arch": arch, "shape": shp,
                 "error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc()[-2000:]}
        results.append(r)
        print(f"[{label}] {r['status']}", flush=True)
        if r["status"] == "ok":
            print(f"  flops/dev={r['flops_per_device']:.3e} "
                  f"bytes/dev={r.get('bytes_accessed_per_device', 0):.3e} "
                  f"compile={r.get('compile_s')}s", flush=True)
        elif r["status"] == "error":
            print("  " + r["error"], flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            suffix = "feddif" if args.feddif else (
                "512" if mp else "256")
            path = os.path.join(args.out, f"dryrun_{arch}_{shp}_{suffix}.json")
            with open(path, "w") as f:
                json.dump(r, f, indent=1)
    bad = [r for r in results if r["status"] == "error"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
