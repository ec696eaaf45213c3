"""One run of one cell: set-up, warm-up, the measured window, and the
comparison with the reference.

The window drives ``repro.fl.server.run_federated`` once, so warm-up and
window share one executor and compile nothing twice.  The harness owns
the callables the loop calls back: the client batch draws (which count the
rows they hand out) and ``eval_fn``, called after every round, which
evaluates the program's model on the held-out rows, stamps the end of the
round, opens the window once a round has compiled nothing, and stops the
loop by raising :class:`_WindowClosed` once ``seconds`` have passed.

Spans: the scheduler entry of ``repro.fl.schedulers.SCHEDULERS``, the
executor's ``run_round`` (ended by a block on its result), the batch draws
and the eval are wrapped at run time; each wrapper adds to a host timer
and, in a traced run, opens a ``jax.profiler.TraceAnnotation``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import os
import tempfile
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from perf import reference as R
from perf.traffic.generate import make_traffic, sub_seeds

WARMUP_ROUNDS = 3           # the rounds the reference follows
MAX_WARMUP_ROUNDS = 20
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class _WindowClosed(Exception):
    pass


class Spans:
    """Host timers per span name, counted while ``recording``."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.recording = False
        self.seconds: dict = defaultdict(float)
        self.calls: dict = defaultdict(int)

    def wrap(self, name: str, fn, block: bool = False):
        def wrapped(*args, **kwargs):
            ctx = (jax.profiler.TraceAnnotation(name) if self.annotate
                   else contextlib.nullcontext())
            with ctx:
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                if block:
                    jax.block_until_ready(out)
                dt = time.perf_counter() - t0
            if self.recording:
                self.seconds[name] += dt
                self.calls[name] += 1
            return out
        return wrapped


class CompileCounter:
    """Executables the backend hands out (compiled or read from the
    persistent cache), counted through ``jax.monitoring``."""

    count = 0
    _registered = False

    @classmethod
    def install(cls):
        if not cls._registered:
            def listen(event, duration, **kwargs):
                if event == BACKEND_COMPILE:
                    cls.count += 1
            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._registered = True


def load_config(name: str):
    """A configuration's three files: sizes, plain reference, glue."""
    import json
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")
    with open(os.path.join(here, f"{name}.json")) as f:
        conf = json.load(f)
    return (conf, importlib.import_module(f"perf.configs.{name}_ref"),
            importlib.import_module(f"perf.configs.{name}"))


def cpu_sizes(conf: dict) -> dict:
    """The configuration at the sizes of its ``"cpu"`` object, merged
    deeply into it: what the per-cell CPU tests run.  Chip runs use the
    configuration itself."""
    def merge(base: dict, over: dict) -> dict:
        out = dict(base)
        for k, v in over.items():
            out[k] = (merge(base[k], v) if isinstance(v, dict)
                      and isinstance(base.get(k), dict) else v)
        return out
    return merge(conf, conf.get("cpu", {}))


def frozen_tree(conf: dict, ref, seeds: dict):
    """The tree a reference's ``frozen(conf, key)`` builds from the run's
    ``"frozen"`` sub-seed, on the device in one call; None where the
    reference defines no ``frozen``."""
    if not hasattr(ref, "frozen"):
        return None
    build = jax.jit(lambda k: ref.frozen(conf, k))
    return jax.block_until_ready(build(jax.random.PRNGKey(seeds["frozen"])))


@dataclasses.dataclass
class Records:
    """What the first rounds and the window left for the check and the
    metrics."""
    stamps: list = dataclasses.field(default_factory=list)
    compiles: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    global3: object = None              # global params after round 3
    grad1: list | None = None           # momentum after the first step
    schedules: list = dataclasses.field(default_factory=list)
    ledger: dict | None = None
    ledger_obj: object = None
    window_start: float | None = None
    window_end: float | None = None
    window_rounds: int = 0
    window_compiles: int = 0
    rows_window: int = 0
    frozen: object = None               # the frozen tree, or None


def _engine(mix: dict):
    from repro.fl.engine import EngineSpec
    return EngineSpec(mode=mix["engine"], planner=mix["planner"],
                      mesh_model_axis=int(mix.get("mesh_model_axis", 1)))


def fl_config(mix: dict, seeds: dict):
    from repro.fl.server import FLConfig
    return FLConfig(
        strategy=mix["strategy"], num_clients=int(mix["clients"]),
        num_models=int(mix["models"]), rounds=1 << 30,
        lr=float(mix["lr"]), momentum=float(mix["momentum"]),
        batch_size=int(mix["batch_size"]), epsilon=float(mix["epsilon"]),
        gamma_min=float(mix["gamma_min"]),
        max_diffusion_rounds=int(mix["max_diffusion_rounds"]),
        seed=seeds["init"], topology_seed=seeds["topology"],
        eval_every=int(mix["eval_every"]), scenario=mix["scenario"],
        engine=_engine(mix))


def drive(conf: dict, ref, glue, mix: dict, seed: int,
          seconds: float, trace_dir: str | None,
          fault: str | None = None, stop_after: int | None = None):
    """Run the program on one cell; returns (records, traffic, spans,
    seeds).  ``stop_after`` ends the loop after that many rounds, with no
    window; ``fault`` breaks the program's timed path on purpose (tests
    and the readings of step 3 of the check)."""
    import repro.fl.server as server
    from repro.data.pipeline import make_client_loaders
    from repro.fl.schedulers import SCHEDULERS

    CompileCounter.install()
    jax.clear_caches()                  # programs an earlier run left loaded
    gc.collect()
    seeds = sub_seeds(seed)
    traffic = make_traffic(mix, conf["data"], seed)
    rec = Records(frozen=frozen_tree(conf, ref, seeds))
    # A configuration with a frozen tree hands the program the same arrays.
    loss_fn, evaluate, shapes = (
        glue.program(conf, mix) if rec.frozen is None
        else glue.program(conf, mix, rec.frozen))
    if fault == "half_batch":
        inner = loss_fn

        def loss_fn(params, batch):
            return inner(params, jax.tree.map(
                lambda a: a[: a.shape[0] // 2], batch))
    init = jax.jit(lambda k: ref.init(conf, k))
    params0 = jax.block_until_ready(init(jax.random.PRNGKey(seeds["init"])))
    # Unload the init program: a full-width model's session needs every
    # byte of device memory the program's own runs leave.
    del init
    jax.clear_caches()
    want = jax.tree.map(lambda a: (a.shape, a.dtype), shapes())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params0)
    if want != got:
        raise ValueError("the configuration's parameter layout is not the "
                         "program's")

    spans = Spans(annotate=trace_dir is not None)
    batch_span = spans.wrap("batch", lambda ld: list(ld.epoch()))

    def draws(loader):
        def draw():
            batches = batch_span(loader)
            if spans.recording:
                rec.rows_window += sum(int(b["x"].shape[0]) for b in batches)
            return batches
        return draw

    loaders = make_client_loaders(traffic.train, traffic.part,
                                  int(mix["batch_size"]),
                                  seed=seeds["loader"])
    client_batches = [draws(ld) for ld in loaders]
    test_x = jax.device_put(traffic.test_x)
    test_y = jax.device_put(traffic.test_y)
    eval_jit = jax.jit(evaluate)
    eval_span = spans.wrap("eval", lambda p: eval_jit(p, test_x, test_y),
                           block=True)

    def eval_fn(params):
        acc, loss = eval_span(params)
        now = time.perf_counter()
        rec.stamps.append(now)
        rec.compiles.append(CompileCounter.count)
        r = len(rec.stamps)
        if r <= WARMUP_ROUNDS:
            rec.losses.append(float(loss))
        if r == WARMUP_ROUNDS:
            rec.global3 = jax.device_get(params)
            rec.ledger = dict(rec.ledger_obj.as_dict())
        if stop_after is not None and r >= stop_after:
            raise _WindowClosed
        if rec.window_start is None:
            quiet = r >= 2 and rec.compiles[-1] == rec.compiles[-2]
            if r >= WARMUP_ROUNDS and (quiet or r >= MAX_WARMUP_ROUNDS):
                if trace_dir is not None:
                    jax.profiler.start_trace(trace_dir)
                spans.recording = True
                rec.window_compiles = CompileCounter.count
                rec.window_start = time.perf_counter()
        else:
            rec.window_rounds += 1
            if now - rec.window_start >= seconds:
                rec.window_end = now
                spans.recording = False
                rec.window_compiles = CompileCounter.count - \
                    rec.window_compiles
                if trace_dir is not None:
                    jax.profiler.stop_trace()
                raise _WindowClosed
        return float(acc), float(loss)

    strategy = mix["strategy"]
    orig_sched = SCHEDULERS[strategy]
    plan_span = spans.wrap("plan", orig_sched)

    def scheduler(ctx):
        sched = plan_span(ctx)
        if len(rec.schedules) < WARMUP_ROUNDS:
            rec.schedules.append(sched)
        return sched

    orig_make, orig_charge = server.make_executor, server.charge_schedule

    def make_executor(*args, **kwargs):
        ex = orig_make(*args, **kwargs)
        step = ex._step

        def first_step(*a):
            # The step donates its state, so the first step's momentum is
            # copied before the next step is dispatched.
            out = step(*a)
            if rec.grad1 is None:
                rec.grad1 = [jax.device_get(out[1])]
            return out
        ex._step = first_step
        if fault == "no_hop":
            ex._permute = lambda params, op: params
        run_round = ex.run_round
        if fault == "unchanged":
            def run_round(sched, global_params, slots):
                return global_params, None
        ex.run_round = spans.wrap("exec", run_round, block=True)
        return ex

    def charge(ledger, sched):
        rec.ledger_obj = ledger
        return orig_charge(ledger, sched)

    SCHEDULERS[strategy] = scheduler
    server.make_executor, server.charge_schedule = make_executor, charge
    # The loop takes the only reference to the initial weights, so they are
    # freed once round 1 replaces them, as the program's own init would be.
    handed = [params0]
    del params0
    try:
        server.run_federated(lambda key: handed.pop(), loss_fn,
                             client_batches,
                             traffic.part.dsi, traffic.part.data_sizes,
                             eval_fn, fl_config(mix, seeds))
    except _WindowClosed:
        pass
    finally:
        SCHEDULERS[strategy] = orig_sched
        server.make_executor, server.charge_schedule = (orig_make,
                                                        orig_charge)
        spans.recording = False
    rec.ledger_obj = None
    del test_x, test_y, loaders, client_batches
    gc.collect()
    return rec, traffic, spans, seeds


def reference_globals(conf: dict, ref, glue, mix: dict, traffic, seeds: dict,
                      schedules: list, compute_dtype="float32",
                      param_dtype="float32", frozen=None):
    """The reference's initial params (host), its global params after each
    of the first rounds, its first gradient at every slot, the schedule
    faults it found, and its ledger.  ``frozen`` is the run's frozen tree,
    the program's own arrays."""
    init = jax.jit(lambda k: ref.init(conf, k))
    params0 = init(jax.random.PRNGKey(seeds["init"]))
    bits = R.model_bits(params0)
    sizes = np.asarray(traffic.part.data_sizes, np.float64)
    n = int(mix["clients"])
    d2d_rounds = R.strategy_ref(mix["strategy"]).d2d_rounds(mix)
    world = R.world_ref(mix["scenario"])
    ledger, plans, faults = R.Ledger(), [], []
    for t, sched in enumerate(schedules):
        up, d2d = world.round_channels(seeds["topology"], t, n, d2d_rounds)
        plan = R.check_schedule(sched, mix, sizes, up, d2d, bits, ledger)
        faults += plan.faults
        plans.append(plan)
    trainer = R.Trainer(ref, conf, mix, jnp.dtype(compute_dtype),
                        jnp.dtype(param_dtype), glue.SLOT_BLOCK, frozen)
    host0 = jax.device_get(params0)
    grad1 = R.first_gradient(mix, traffic, seeds["loader"], params0, trainer)
    globals3 = R.replay(ref, conf, mix, traffic, seeds["loader"], params0,
                        plans, trainer)
    return host0, globals3, grad1, faults, ledger


def program_reading(rec, params0) -> R.Reading:
    """The program's side of the comparison, from a run's records and the
    initial weights (host)."""
    return R.Reading(losses=rec.losses, grad1=rec.grad1,
                     norms3=R.leaf_change_norms(rec.global3, params0))


def check(conf, ref, glue, mix, rec, traffic, seeds) -> dict:
    """The numbers that decide ``correct``."""
    jax.clear_caches()                  # the program's programs, unloaded
    gc.collect()
    params0, ref_g, grad1, faults, ledger = reference_globals(
        conf, ref, glue, mix, traffic, seeds, rec.schedules,
        frozen=rec.frozen)
    ref_reading = R.reading_of(ref, conf, traffic, params0, ref_g, grad1,
                               glue.EVAL_BLOCK, frozen=rec.frozen)
    numbers = R.compare(program_reading(rec, params0), ref_reading)
    numbers["ledger_gap"] = ledger.gap(rec.ledger)
    numbers["schedule_faults"] = len(faults)
    numbers.pop("leaves_left_out")
    return numbers


def trace_dir_for(run_trace: bool):
    """A private directory for the profiler, under ``TMPDIR``."""
    if not run_trace:
        return contextlib.nullcontext(None)
    return tempfile.TemporaryDirectory(prefix="perf_trace_")


def memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)
