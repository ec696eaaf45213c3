"""Run the FedDif main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: kernels, FedDif, smollm_360m
    python chip_smoke.py --chips 4    # sharded engine on 4 chips vs fleet on 1

One process drives every device (it starts no child that needs the chip).
It refuses to run unless JAX's first device is a TPU.  Data and weights
come from ``--seed``; nothing outside the tracked tree is read.

One-chip phases, each printing one progress line:

* ``devices``         platform, device kind and count.
* ``kernels``         each FL data-plane kernel compiled for the chip
  (``implementation="pallas"``) against its reference twin at the widths
  the phases below use.  quant_pack/unpack must match bit for bit; the
  others within the stated tolerance.
* ``feddif_cnn``      FedDif on the fleet engine with the jax planner: the
  paper's FMNIST-shaped CNN (dim 784, 10 classes), 20 clients and models,
  3 rounds.  Losses finite and falling; the ledger equals the host
  engine's on the same config exactly.
* ``feddif_lm_int8``  the same on the LoRA LM task with int8-packed hops.
* ``smollm_360m``     the zoo trainer (``repro.launch.train``) on the full
  smollm_360m config, fleet engine, 2 clients, 2 rounds of 2 steps.

``--chips 4`` runs only ``sharded_4chip``: FedDif at N=256 (the fused
round plane) on the LM task with int8 hops over a (2 clients x 2 model)
mesh, against the fleet engine on the first chip.  Ledgers identical,
final params within the stated tolerance, and a profiler trace shows
collectives on all four chips.

Times, compile seconds and peak bytes printed on the way are smoke
readings, not benchmark numbers.  The last line of standard output is
``{"ok": true, "device": {...}}`` only when every phase passed.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Stated tolerances of the compiled kernels against their reference twins
# (relative ones on the largest reference magnitude).  References and
# kernels both take fp32-exact ("highest") matmuls, so the tolerances admit
# summation order only; one bf16 MXU pass errs by ~3e-3 relative and fails.
MIX_RTOL = 1e-4         # weighted fp32 sum of C terms
STC_RTOL = 1e-5         # elementwise, with an fp32 survivor mean
BID_ATOL = 2e-5         # centered expansion vs the broadcast composite
FUSE_RTOL = 1e-6        # elementwise
# Sharded (4 chips) vs fleet (1 chip) final global params: the two planes
# batch the same client steps differently.  A sound run on four v5e chips
# differs by 1.1e-4 (1.1e-5 on a forced 4-device CPU mesh); rounding the
# sharded plane's mix and aggregate inputs to bf16, as one bf16 MXU pass
# does, moves the CPU mesh's difference to 2.9e-3, which must fail.
SHARDED_PARAMS_ATOL = 1e-3

SEQ_LEN = 32            # the LoRA LM task's sequence length (fig_lm)
CNN_DIM = 784           # FMNIST 28x28, the paper's CNN input
CLASSES = 10


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _round_readings(result) -> str:
    walls = list(result.round_wall_s)
    steady = sorted(walls[1:])[len(walls[1:]) // 2] if len(walls) > 1 \
        else float("nan")
    return (f"smoke reading: round 1 (with compile) {walls[0]:.2f} s, "
            f"steady {steady:.2f} s/round, "
            f"peak_bytes_in_use {_peak_bytes()}")


# ------------------------------------------------------------------ phases

def phase_devices() -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("devices", f"platform={dev['platform']} kind={dev['kind']} "
                   f"count={dev['count']}")
    return dev


def phase_kernels(seed: int, impl: str = "pallas", smollm_f: int | None = None
                  ) -> None:
    """Each data-plane kernel through ``kernels.ops`` against its twin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fl.models import build_task_model
    from repro.kernels import ops
    from repro.kernels.quant import QUANT_BLOCK

    key = jax.random.PRNGKey(seed)
    keys = iter(jax.random.split(key, 32))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32)

    def err(a, b) -> tuple[float, float]:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b))), float(np.max(np.abs(b)))

    def ref(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, implementation="ref", **kw)

    cnn_f = _flat_size(build_task_model("cnn", CNN_DIM, CLASSES))
    lm_f = _flat_size(build_task_model("lm", SEQ_LEN, CLASSES), adapter=True)
    mix_cases = [("cnn_c20_mix", 20, cnn_f, 20), ("cnn_c20_agg", 20, cnn_f, 1),
                 ("lm_c20_agg", 20, lm_f, 1)]
    if smollm_f is not None:
        mix_cases.append(("smollm_c2_agg", 2, smollm_f, 1))
    for name, c, f, g in mix_cases:
        x, w = normal((c, f)), uniform((g, c))
        e, m = err(ops.mix_aggregate(x, w, implementation=impl),
                   ref(ops.mix_aggregate, x, w))
        say("kernels", f"mix_aggregate {name} C={c} F={f} G={g}: "
                       f"max_abs_err={e:.3e} (max|ref|={m:.3e}, "
                       f"tol {MIX_RTOL:g}*max|ref|)")
        check(e <= MIX_RTOL * m, f"mix_aggregate {name}: error {e}")
        del x, w

    for c, n in ((20, 100_000), (3, 8192 + 7)):
        x, r = normal((c, n)), normal((n,))
        mask = jnp.arange(c) % 3 != 1
        e, m = err(ops.stc_topk(x, r, mask, 0.01, implementation=impl),
                   ref(ops.stc_topk, x, r, mask, 0.01))
        say("kernels", f"stc_topk C={c} n={n}: max_abs_err={e:.3e} "
                       f"(tol {STC_RTOL:g}*max|ref|)")
        check(e <= STC_RTOL * m, f"stc_topk C={c}: error {e}")

    for m_, n_ in ((20, 20), (256, 4096)):
        dol = jax.random.dirichlet(next(keys), jnp.ones(CLASSES), (m_,))
        dsi = jax.random.dirichlet(next(keys), jnp.ones(CLASSES), (n_,))
        chain = jnp.floor(uniform((m_,)) * 500.0)
        sizes = jnp.floor(uniform((n_,)) * 300.0)
        e, _ = err(ops.dol_bid_scores(dol, chain, dsi, sizes,
                                      implementation=impl),
                   ref(ops.dol_bid_scores, dol, chain, dsi, sizes))
        say("kernels", f"dol_bid_scores M={m_} N={n_}: max_abs_err={e:.3e} "
                       f"(tol {BID_ATOL:g})")
        check(e <= BID_ATOL, f"dol_bid_scores M={m_}: error {e}")
        bids, value = normal((m_, n_)), uniform((n_,))
        e, m = err(ops.bid_value_fuse(bids, value, 0.7, implementation=impl),
                   ref(ops.bid_value_fuse, bids, value, 0.7))
        say("kernels", f"bid_value_fuse M={m_} N={n_}: max_abs_err={e:.3e} "
                       f"(tol {FUSE_RTOL:g}*max|ref|)")
        check(e <= FUSE_RTOL * m, f"bid_value_fuse M={m_}: error {e}")

    for rows in (20 * -(-lm_f // QUANT_BLOCK), 57):
        x = normal((rows, QUANT_BLOCK)) * uniform((rows, 1)) * 10.0
        x = x.at[0].set(0.0)
        q, s = ops.quant_pack(x, implementation=impl)
        q_r, s_r = ops.quant_pack(x, implementation="ref")
        out = ops.quant_unpack(q, s, implementation=impl)
        out_r = ops.quant_unpack(q_r, s_r, implementation="ref")
        n_codes = int(np.sum(np.asarray(q) != np.asarray(q_r)))
        n_scales = int(np.sum(np.asarray(s) != np.asarray(s_r)))
        n_out = int(np.sum(np.asarray(out) != np.asarray(out_r)))
        say("kernels", f"quant_pack/unpack R={rows} B={QUANT_BLOCK}: "
                       f"codes differing={n_codes} scales differing="
                       f"{n_scales} decoded differing={n_out} "
                       f"(max_abs_err={err(out, out_r)[0]:.3e}; bitwise)")
        check(n_codes == n_scales == n_out == 0,
              f"quant R={rows}: not bitwise equal to the reference")


def _flat_size(model, adapter: bool = False) -> int:
    import jax
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if adapter:
        _, shapes = model.split(shapes)
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


# Learning rates at which three rounds visibly lower the eval loss (the
# library default 0.01 moves the LoRA LM's loss by ~1e-6 in three rounds).
LR = {"cnn": 0.05, "lm": 0.5}


def _feddif_spec(task: str, dim: int, num_samples: int, clients: int,
                 rounds: int, seed: int, engine, hop_quant: str = "none"):
    from repro.fl import ExperimentSpec, FLConfig
    return ExperimentSpec(
        task=task, dim=dim, num_classes=CLASSES, alpha=0.5,
        num_samples=num_samples, data_seed=seed,
        fl=FLConfig(strategy="feddif", rounds=rounds, num_clients=clients,
                    num_models=clients, lr=LR[task], seed=seed,
                    topology_seed=seed, max_diffusion_rounds=4,
                    hop_quant=hop_quant, engine=engine))


def phase_feddif(phase: str, task: str, dim: int, num_samples: int,
                 seed: int, hop_quant: str = "none") -> None:
    """FedDif on the fleet engine (jax planner) vs the host engine: 20
    clients and models, 3 rounds."""
    from repro.fl import run_experiment
    from repro.fl.engine import EngineSpec

    rounds, runs = 3, {}
    for mode in ("fleet", "host"):
        spec = _feddif_spec(task, dim, num_samples, 20, rounds, seed,
                            EngineSpec(mode=mode, planner="jax"), hop_quant)
        t0 = time.time()
        r = run_experiment(spec)
        runs[mode] = r
        losses = [float(v) for v in r.loss]
        say(phase, f"{mode} engine: loss per round {losses}, "
                   f"acc {[round(float(a), 4) for a in r.accuracy]}, "
                   f"diffusion rounds {list(r.diffusion_rounds)}, "
                   f"wall {time.time() - t0:.1f} s; {_round_readings(r)}")
        check(len(losses) == rounds and all(map(math.isfinite, losses)),
              f"{phase} {mode}: non-finite loss {losses}")
        check(losses[-1] < losses[0],
              f"{phase} {mode}: final loss {losses[-1]} not below round "
              f"1's {losses[0]}")
    led_f, led_h = (runs["fleet"].ledger.as_dict(),
                    runs["host"].ledger.as_dict())
    say(phase, f"ledger fleet == host: {led_f == led_h} "
               f"(subframes {led_f.get('subframes')}, transmitted_bits "
               f"{led_f.get('transmitted_bits')})")
    check(led_f == led_h, f"{phase}: ledgers differ: {led_f} vs {led_h}")


def phase_smollm(seed: int) -> None:
    """The zoo trainer path at the full smollm_360m width."""
    from repro.launch import train
    t0 = time.time()
    result = train.main(["--arch", "smollm_360m", "--engine", "fleet",
                         "--clients", "2", "--rounds", "2",
                         "--steps-per-round", "2", "--seed", str(seed)])
    losses = [float(v) for v in result.loss]
    say("smollm_360m", f"fleet engine, 2 clients: eval loss per round "
                       f"{losses}, wall {time.time() - t0:.1f} s; "
                       f"{_round_readings(result)}")
    check(len(losses) == 2 and all(map(math.isfinite, losses)),
          f"smollm_360m: non-finite loss {losses}")
    check(result.ledger.transmitted_bits > 0, "smollm_360m: empty ledger")


_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter")


def _devices_with_collectives(trace_dir: str) -> set:
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    check(bool(files), "no profiler trace was written")
    found = set()
    for path in files:
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            if any(_COLLECTIVE.search(ev.name) for line in plane.lines
                   for ev in line.events):
                found.add(plane.name)
    return found


def phase_sharded(seed: int, trace: bool = True) -> None:
    """Sharded engine over every device vs the fleet engine on device 0:
    FedDif at N=256, 2 rounds.  ``trace=False`` skips the profiler check
    (a CPU rehearsal has no TPU planes to find collectives on)."""
    import jax
    import numpy as np

    from repro.fl import run_experiment
    from repro.fl.engine import EngineSpec

    phase, clients, runs = "sharded_4chip", 256, {}
    for name, engine in (("sharded", EngineSpec(mode="sharded",
                                                mesh_model_axis=2)),
                         ("fleet", EngineSpec(mode="fleet"))):
        spec = _feddif_spec("lm", SEQ_LEN, 48 * clients, clients, 2, seed,
                            engine, hop_quant="int8")
        t0 = time.time()
        if name == "sharded" and trace:
            with tempfile.TemporaryDirectory() as d:
                with jax.profiler.trace(d):
                    r = run_experiment(spec)
                devs = _devices_with_collectives(d)
            say(phase, f"devices running collectives in the sharded "
                       f"trace: {sorted(devs)}")
            check(len(devs) == len(jax.devices()) == 4,
                  f"collectives span {len(devs)} devices, not 4")
        else:
            r = run_experiment(spec)
        runs[name] = r
        losses = [float(v) for v in r.loss]
        say(phase, f"{name} engine N={clients}: loss per round {losses}, "
                   f"wall {time.time() - t0:.1f} s; {_round_readings(r)}")
        check(all(map(math.isfinite, losses)), f"{name}: non-finite loss")
    led_s, led_f = (runs["sharded"].ledger.as_dict(),
                    runs["fleet"].ledger.as_dict())
    diffs = [float(np.max(np.abs(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64))))
             for a, b in zip(jax.tree.leaves(runs["sharded"].final_params),
                             jax.tree.leaves(runs["fleet"].final_params))]
    say(phase, f"ledger sharded == fleet: {led_s == led_f} "
               f"(subframes {led_s.get('subframes')}); final params max "
               f"abs diff {max(diffs):.3e} (tol {SHARDED_PARAMS_ATOL:g})")
    check(led_s == led_f, f"ledgers differ: {led_s} vs {led_f}")
    check(max(diffs) <= SHARDED_PARAMS_ATOL,
          f"final params differ by {max(diffs)}")


# -------------------------------------------------------------------- main

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "engine across four chips against the fleet "
                         "engine on one")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: the repository's src/ is not next to this "
              "script", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    if args.chips == 4:
        phases = [("sharded_4chip", lambda: phase_sharded(args.seed))]
    else:
        from repro.configs import get_config
        from repro.models import build_model
        smollm_f = _flat_size(build_model(get_config("smollm_360m")))
        phases = [
            ("kernels", lambda: phase_kernels(args.seed, smollm_f=smollm_f)),
            ("feddif_cnn", lambda: phase_feddif(
                "feddif_cnn", "cnn", CNN_DIM, 6000, args.seed)),
            ("feddif_lm_int8", lambda: phase_feddif(
                "feddif_lm_int8", "lm", SEQ_LEN, 3840, args.seed,
                hop_quant="int8")),
            ("smollm_360m", lambda: phase_smollm(args.seed)),
        ]
    device = phase_devices()
    failed = []
    for name, run in phases:
        t0 = time.time()
        try:
            run()
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            say(name, f"FAILED after {time.time() - t0:.1f} s")
            continue
        say(name, f"passed in {time.time() - t0:.1f} s (smoke reading)")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
