"""One run of one cell: set-up, warm-up, a timed or traced window, the check.

:func:`measure` is the whole run behind ``bench/run.py``.  It builds the
cell's graph from the seed on the device, constructs one
``repro.solve.Solver`` on the program's default backend, warms it up with
one solve (which compiles, or loads from the compile cache), and then either
solves back to back until ``seconds`` have passed (``trace=False``) or
profiles a few solves (``trace=True``).  Once the window has closed and the
device's peak memory has been read, every sampled answer is compared with
the host reference (``bench.reference``) by the cell's problem module.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import shutil
import sys
import time

import numpy as np

from bench.spec import BENCH, Cell

TRACE_ROOT = BENCH / ".traces"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Solve:
    label: object
    rounds: int
    converged: bool


@dataclasses.dataclass
class Run:
    """What metric readers read (``bench/metrics``)."""

    device_kind: str
    vertices: int
    edges: int
    setup_s: float
    schedule_build_s: float
    compile_s: float
    timed: list  # [Solve] of the window, in order
    window_s: float
    trace: object = None  # bench.trace_reduce.Reduction of a traced run


def log(msg: str, device: str = "") -> None:
    """A line on standard error, naming the device once it is known."""
    print(f"[bench{' ' + device if device else ''}] {msg}", file=sys.stderr, flush=True)


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"platform {d.platform}; device_kind {d.device_kind}; count {len(devices)}")
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {d.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


class _Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from ``rng``."""

    def __init__(self, k, rng):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0

    def offer(self, item):
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.items[j] = item


def _window(solver, draws, sample, until):
    """Solve back to back until ``until(count, elapsed)``; spans per solve."""
    from jax.profiler import TraceAnnotation

    timed = []
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.draw"):
                label, x0 = next(draws)
            with TraceAnnotation("bench.solve"):
                res = solver.solve(x0)
            timed.append(Solve(label, res.rounds, res.converged))
            sample.offer((label, res.x, res.converged))
            if until(len(timed), time.perf_counter() - t0):
                break
    return timed, time.perf_counter() - t0


@contextlib.contextmanager
def _compiles():
    """A list that grows by one for every compile or compile-cache load."""
    import jax

    events = []

    def listener(event, duration, **kwargs):
        if "compile" in event or "cache_retrieval" in event:
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
            require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    from bench import graphgen, trace_reduce

    devices = _devices(cell.chips, require_tpu)
    tag = f"{devices[0].platform}/{devices[0].device_kind}/x{len(devices)}"
    from repro.graphs.formats import CSRGraph
    from repro.launch.compile_cache import enable_compile_cache
    from repro.solve import Solver

    log(f"compile cache: {enable_compile_cache()}", tag)
    problem = importlib.import_module(f"bench.problems.{cell.traffic['problem']}")

    t = time.perf_counter()
    sym = graphgen.generate(cell.config, seed)
    log(f"graph {cell.config['name']}: n={sym.n} E={sym.edges} in "
        f"{time.perf_counter() - t:.3f} s", tag)
    graph = CSRGraph(
        n=sym.n, indptr=sym.indptr, indices=sym.indices,
        values=problem.edge_values(sym, cell.traffic), name=cell.config["name"],
    )
    delta = int(cell.traffic["delta"])
    solver = Solver(graph, problem.problem(cell.traffic),
                    n_workers=int(cell.traffic["n_workers"]), delta=delta)
    t = time.perf_counter()
    sched = solver.schedule(delta)
    schedule_build_s = time.perf_counter() - t
    log(f"schedule δ={sched.delta} S={sched.S} P={sched.P} M={sched.M}: "
        f"padding {sched.padding_overhead:.4f}x in {schedule_build_s:.3f} s", tag)
    draws = problem.draws(sym, cell.traffic, seed)
    _, x0 = next(draws)
    warm = solver.solve(x0)
    setup_s = time.perf_counter() - t_start
    compile_s = float(solver.stats["compile_time_s"])
    log(f"warm-up solve: {warm.rounds} rounds; compile {compile_s:.3f} s; "
        f"set-up {setup_s:.3f} s", tag)

    rng = np.random.default_rng([int(seed), 2])
    sample = _Reservoir(int(cell.traffic["check_solves"]), rng)
    reduction = None
    with _compiles() as compiles:
        if trace:
            trace_dir = TRACE_ROOT / cell.name
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            k = int(cell.traffic["trace_solves"])
            jax.profiler.start_trace(str(trace_dir))
            try:
                timed, window_s = _window(solver, draws, sample, lambda c, _: c >= k)
            finally:
                jax.profiler.stop_trace()
        else:
            timed, window_s = _window(solver, draws, sample, lambda _, e: e >= seconds)
    rounds = np.unique([s.rounds for s in timed], return_counts=True)
    log(f"window: {len(timed)} solves in {window_s:.3f} s, rounds "
        f"{dict(zip(*(a.tolist() for a in rounds)))}, compiles inside {len(compiles)}", tag)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    del solver, sched, warm
    gc.collect()
    if trace:
        reduction = trace_reduce.reduce(trace_reduce.load(TRACE_ROOT / cell.name))

    run = Run(
        device_kind=devices[0].device_kind, vertices=sym.n, edges=sym.edges,
        setup_s=setup_s, schedule_build_s=schedule_build_s, compile_s=compile_s,
        timed=timed, window_s=window_s, trace=reduction,
    )
    metrics = {}
    for name in cell.metrics(trace):
        module = importlib.import_module(f"bench.metrics.{name}")
        value = module.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell.units[name]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    checks, wrong = _check(problem, cell, sym, sample.items, timed, tag)
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": len(timed),
        "failed": sum(not s.converged for s in timed) + wrong,
        "metrics": metrics,
        "device": device,
        "window_compiles": len(compiles),
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in reduction.device_ops],
            "idle_gaps": [list(kv) for kv in reduction.idle_gaps],
        }
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def _check(problem, cell, sym, sample, timed, tag):
    """The sampled answers against the reference.

    Returns ``{number: (value, limit)}`` over the sample, and how many
    converged answers of the sample failed on their own.
    """
    from bench.reference import ReferencePool

    labels = list(dict.fromkeys(label for label, _, _ in sample))
    workers = max(1, min(len(labels), int(cell.traffic["reference_workers"])))
    t = time.perf_counter()
    with ReferencePool(sym, workers) as pool:
        futures = {
            label: problem.reference(pool, label, cell.traffic, False)
            for label in labels
        }
        refs = {label: f.result() for label, f in futures.items()}
    log(f"reference: {len(sample)} answers, {len(labels)} references, "
        f"{workers} workers, {time.perf_counter() - t:.3f} s", tag)
    answers = [x for _, x, _ in sample]
    want = [refs[label] for label, _, _ in sample]
    checks = problem.compare(answers, want, cell.traffic)
    checks["unconverged"] = (sum(not s.converged for s in timed), 0)
    wrong = sum(
        converged
        and any(v > lim for v, lim in problem.compare([x], [r], cell.traffic).values())
        for (_, x, converged), r in zip(sample, want)
    )
    return checks, wrong

