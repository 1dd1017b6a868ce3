#!/usr/bin/env python3
"""Smoke run of the delayed-commit graph engine on a TPU.

Drives the main path once through the entry points a user calls, at a size
users would call real, and checks every answer against an independent host
reference (numpy/scipy).  Nothing it prints is a benchmark metric: the lines
show that the path runs and what it cost, on the device named at the top.

    python chip_smoke.py              # one chip: GAP kron scale 20
    python chip_smoke.py --chips 4    # four chips: kron scale 21, halo solves

One chip: ``Solver(n_workers=8, delta="auto")`` on its default ``jit``
backend solves PageRank, then SSSP; then a ``GraphService`` answers a batch
of SSSP and personalized-PageRank queries through ``submit``/``drain``.
Four chips: ``Solver(backend="sharded", frontier="halo", delta=128)`` over
all four chips solves SSSP and PageRank on a graph whose stripes no single
chip holds, with each chip receiving only its own shard of the schedule.

The graph is GAP's kron (Graph500 Kronecker parameters A=.57, B=.19, C=.19,
edge factor 16), cut from GAP's scale 27 to what the stripe padding lets fit.
Exits non-zero, without the final JSON line, when no TPU is found or any
check fails.  ``--rehearse --scale N`` runs the same phases on the CPU at a
tiny size before spending chip time; it never prints the final JSON line.
"""

from __future__ import annotations

import argparse
import gc
import json
import multiprocessing
import sys
import time
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: PageRank-family answers: L1 distance to the float64 reference.  The engine
#: stops at an L1 round change of tol = 1e-4, which bounds its L1 error by
#: tol·d/(1-d) ≈ 5.7e-4 at d = 0.85; 1e-3 leaves room for float32 sums.
PR_L1_TOL = 1e-3
#: SSSP answers: integer path lengths, compared exactly (relative tol 0).
SSSP_RTOL = 0.0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"CHECK FAILED: {msg}")


# --------------------------------------------------------------------------- #
# independent host references (float64 / scipy), never the engine's code.
# They run in spawned worker processes, which import no JAX and so never
# touch the chip, while the main process drives the device.
# --------------------------------------------------------------------------- #
INT_INF = 2**30 - 1  # the engine's min-plus infinity (repro.core.semiring)


def pull_matrix(csr):
    """``A[u, v]`` = value of edge ``v -> u`` from ``(n, indptr, indices, values)``."""
    import scipy.sparse as sp

    n, indptr, indices, values = csr
    return sp.csr_matrix((values.astype(np.float64), indices, indptr), shape=(n, n))


def ref_pagerank(csr, seed=None, damping=0.85, tol=1e-9, max_iter=1000):
    """Fixed point of ``x = teleport + A x`` by power iteration, float64.

    ``seed=None`` teleports uniformly (PageRank); a vertex id teleports all
    ``1 - d`` mass there (personalized PageRank).
    """
    A = pull_matrix(csr)
    n = A.shape[0]
    if seed is None:
        teleport = np.full(n, (1.0 - damping) / n)
    else:
        teleport = np.zeros(n)
        teleport[seed] = 1.0 - damping
    x = np.full(n, 1.0 / n)
    for _ in range(max_iter):
        x_new = teleport + A @ x
        if np.abs(x_new - x).sum() <= tol:
            return x_new
        x = x_new
    raise RuntimeError("PageRank reference did not converge")


def ref_sssp(csr, source):
    """Dijkstra distances from ``source`` (unreachable = the engine's INF)."""
    from scipy.sparse.csgraph import dijkstra

    forward = pull_matrix(csr).T.tocsr()  # [v, u] = length of edge v -> u
    d = dijkstra(forward, directed=True, indices=int(source))
    return np.where(np.isinf(d), float(INT_INF), d)


def csr_of(graph):
    return (graph.n, graph.indptr, graph.indices, graph.values)


def compare_pr(name, got, want):
    l1 = float(np.abs(got.astype(np.float64) - want).sum())
    mx = float(np.abs(got.astype(np.float64) - want).max())
    log(f"  {name}: L1 to reference {l1:.3e} (limit {PR_L1_TOL:g}), max |diff| {mx:.3e}")
    check(l1 <= PR_L1_TOL, f"{name} L1 {l1} > {PR_L1_TOL}")


def compare_sssp(name, got, want):
    diff = np.abs(got.astype(np.float64) - want)
    rel = float((diff / np.maximum(want, 1.0)).max())
    log(
        f"  {name}: max |diff| {float(diff.max()):.0f}, max relative diff "
        f"{rel:.3e} (limit {SSSP_RTOL:g}), reachable {int((want < INT_INF).sum())}"
    )
    check(rel <= SSSP_RTOL, f"{name} max relative diff {rel} > {SSSP_RTOL}")


# --------------------------------------------------------------------------- #
# reporting helpers
# --------------------------------------------------------------------------- #
def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(
            f"{d.id}: peak {st.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB "
            f"in use {st.get('bytes_in_use', 0) / 2**30:.3f} GiB"
        )
    return "  device memory: " + "; ".join(parts)


def schedule_line(sched) -> str:
    from repro.core.engine import schedule_args

    nbytes = sum(int(a.nbytes) for a in schedule_args(sched))
    return (
        f"  schedule δ={sched.delta} S={sched.S} P={sched.P} M={sched.M}: "
        f"padding_overhead {sched.padding_overhead:.2f}x, stripe bytes "
        f"{nbytes} ({nbytes / 2**30:.3f} GiB)"
    )


def solve_report(name, solver, result, wall_s, devices):
    log(
        f"  {name}: rounds {result.rounds} converged {result.converged} "
        f"δ {result.delta} (δ* of delta={solver.default_delta!r})"
    )
    log(
        f"  {name}: time to fixed point {result.total_time_s:.3f} s "
        f"(block_until_ready), compile {result.compile_time_s:.3f} s this solve, "
        f"solver compile total {solver.stats['compile_time_s']:.3f} s, "
        f"solve() wall incl. probes/builds {wall_s:.3f} s"
    )
    log(schedule_line(solver.schedule(result.delta)))
    log(f"  {name}: solver.stats {solver.stats}")
    log(
        f"  {name}: cache_loads {solver.stats['cache_loads']} compiles "
        f"{solver.stats['compiles']} degradations {solver.degradations}"
    )
    log(memory_line(devices))
    check(result.converged, f"{name} did not converge in {result.rounds} rounds")
    check(solver.degradations == [], f"{name} degraded: {solver.degradations}")


def kron_graphs(scale):
    """GAP kron at ``scale``: PageRank edge values and SSSP edge lengths."""
    from repro.graphs.generators import make_graph, sssp_values

    t0 = time.perf_counter()
    g_pr = make_graph("kron", scale=scale, efactor=16, kind="pagerank")
    g_s = g_pr.with_values(sssp_values(g_pr.indices))
    log(
        f"graph kron scale {scale}: n={g_pr.n} edges={g_pr.nnz} "
        f"generated in {time.perf_counter() - t0:.1f} s (host)"
    )
    return g_pr, g_s


def pick_sources(graph, k, seed):
    """``k`` seeded vertices with out-edges (GAP picks non-isolated sources)."""
    rng = np.random.default_rng(seed)
    return rng.choice(np.nonzero(graph.out_degree > 0)[0], size=k, replace=False)


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def analytic_phase(g_pr, g_s, source, refs, devices):
    """PageRank then SSSP through the default Solver (jit backend, δ auto).

    Returns the δ that δ auto chose for each.
    """
    from repro.solve import Solver, pagerank_problem, sssp_problem

    deltas = {}
    t_phase = time.perf_counter()
    log("phase analytic/pagerank: Solver(n_workers=8, delta='auto'), backend jit")
    solver = Solver(g_pr, pagerank_problem(), n_workers=8, delta="auto")
    check(solver.default_backend == "jit", "default backend is not jit")
    t0 = time.perf_counter()
    res = solver.solve()
    solve_report("pagerank", solver, res, time.perf_counter() - t0, devices)
    compare_pr("pagerank", res.x, refs["pagerank"].result())
    deltas["pagerank"] = res.delta
    del solver, res
    gc.collect()
    log(f"phase analytic/pagerank wall {time.perf_counter() - t_phase:.1f} s")

    t_phase = time.perf_counter()
    log(f"phase analytic/sssp: Solver(sssp source {source}), backend jit")
    solver = Solver(g_s, sssp_problem(source=int(source)), n_workers=8, delta="auto")
    t0 = time.perf_counter()
    res = solver.solve()
    solve_report("sssp", solver, res, time.perf_counter() - t0, devices)
    compare_sssp("sssp", res.x, refs["sssp"].result())
    deltas["sssp"] = res.delta
    del solver, res
    gc.collect()
    log(f"phase analytic/sssp wall {time.perf_counter() - t_phase:.1f} s")
    return deltas


def serving_phase(g_pr, g_s, sources, deltas, refs, devices):
    """A GraphService per edge-value kind answers 8 SSSP and 8 PPR queries.

    Each service serves at the δ that δ auto chose for its graph in the
    analytic phase, as a deployment that has already tuned δ would; the
    probe solves δ auto runs first are covered there.
    """
    from repro.launch import QueryRequest
    from repro.launch.serve_graph import GraphService

    for algo, graph, delta in (
        ("sssp", g_s, deltas["sssp"]),
        ("ppr", g_pr, deltas["pagerank"]),
    ):
        t_phase = time.perf_counter()
        log(f"phase serving/{algo}: GraphService(batch_size=8, delta={delta}), 8 queries")
        service = GraphService(
            graph, n_workers=8, delta=delta, batch_size=8, algos=(algo,)
        )
        for v in sources:
            adm = service.submit(QueryRequest(algo=algo, payload=int(v)))
            check(adm.accepted, f"{algo} query {v} rejected: {adm.reason}")
        t0 = time.perf_counter()
        out = service.drain()
        drain_s = time.perf_counter() - t0
        check(len(out) == len(sources), f"{algo}: {len(out)} of {len(sources)} answered")
        sv = service.solver(algo)
        stats = service.stats()[algo]
        rounds = sorted(r.rounds for r in out)
        log(
            f"  {algo}: δ {out[0].delta} rounds per query {rounds}, drain "
            f"{drain_s:.3f} s, latency per query "
            f"{[round(r.latency_s, 3) for r in out]} s"
        )
        log(f"  {algo}: compile total {stats['compile_time_s']:.3f} s")
        log(schedule_line(sv.schedule(out[0].delta)))
        log(f"  {algo}: solver.stats {stats}")
        log(
            f"  {algo}: cache_loads {stats['cache_loads']} compiles "
            f"{stats['compiles']} degradations {sv.degradations}"
        )
        log(memory_line(devices))
        check(all(r.converged for r in out), f"{algo}: a query did not converge")
        check(sv.degradations == [], f"{algo} degraded: {sv.degradations}")
        (first,) = [r for r in out if r.payload == sources[0]]
        compare = compare_sssp if algo == "sssp" else compare_pr
        compare(f"{algo} query {first.payload}", first.x, refs[algo].result())
        del service, sv, out
        gc.collect()
        log(f"phase serving/{algo} wall {time.perf_counter() - t_phase:.1f} s")


def halo_phase(g_pr, g_s, source, refs, devices):
    """SSSP and PageRank on the sharded halo path over every chip."""
    from repro.core.engine import schedule_args
    from repro.dist.engine_sharded import frontier_plan_args
    from repro.solve import Solver, pagerank_problem, sssp_problem

    for name, graph, problem in (
        ("sssp", g_s, sssp_problem(source=int(source))),
        ("pagerank", g_pr, pagerank_problem()),
    ):
        t_phase = time.perf_counter()
        log(f"phase halo/{name}: Solver(backend='sharded', frontier='halo', delta=128)")
        solver = Solver(
            graph, problem, n_workers=8, delta=128, backend="sharded", frontier="halo"
        )
        t0 = time.perf_counter()
        res = solver.solve()
        solve_report(name, solver, res, time.perf_counter() - t0, devices)
        sched = solver.schedule()
        plan = solver.frontier_plan(sched)
        arrays = {id(a): a for a in (*schedule_args(sched), *frontier_plan_args(sched, plan))}
        total = sum(int(a.nbytes) for a in arrays.values())
        per_dev = {d.id: 0 for d in devices}
        for a in arrays.values():
            for sh in a.addressable_shards:
                per_dev[sh.device.id] += int(sh.data.nbytes)
        log(
            f"  {name}: mesh {dict(solver._default_mesh().shape)}, schedule+plan "
            f"{total / 2**30:.3f} GiB in all, per chip "
            + ", ".join(f"{k}: {v / 2**30:.3f} GiB" for k, v in per_dev.items())
        )
        check(max(per_dev.values()) < 0.5 * total, "schedule not split across chips")
        limit = (devices[0].memory_stats() or {}).get("bytes_limit")
        if limit:
            log(f"  {name}: one chip holds {limit / 2**30:.3f} GiB")
            check(total > limit, "the schedule fits one chip: not a four-chip graph")
        compare = compare_sssp if name == "sssp" else compare_pr
        compare(name, res.x, refs[name].result())
        del solver, res, sched, plan, arrays
        gc.collect()
        log(f"phase halo/{name} wall {time.perf_counter() - t_phase:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=None, help="kron scale (default 20, or 21 with --chips 4)")
    ap.add_argument(
        "--rehearse",
        action="store_true",
        help="run on the CPU at a tiny --scale; never prints the final JSON line",
    )
    args = ap.parse_args(argv)
    scale = args.scale or (21 if args.chips == 4 else 20)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}; devices {devices}")
    log(f"platform {dev.platform}; device_kind {dev.device_kind}; count {len(devices)}")
    if args.rehearse:
        check(dev.platform == "cpu", "--rehearse runs on the CPU only")
        check(scale <= 14, "--rehearse is for tiny scales (<= 14)")
    elif dev.platform != "tpu":
        log("no TPU found: this smoke runs only on a TPU")
        return 2
    check(len(devices) >= args.chips, f"need {args.chips} devices, found {len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")

    t_all = time.perf_counter()
    g_pr, g_s = kron_graphs(scale)
    sources = pick_sources(g_s, 8, seed=0)
    src = int(sources[0])
    spawn = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=3, mp_context=spawn) as pool:
        refs = {
            "pagerank": pool.submit(ref_pagerank, csr_of(g_pr)),
            "sssp": pool.submit(ref_sssp, csr_of(g_s), src),
        }
        if args.chips == 4:
            halo_phase(g_pr, g_s, src, refs, devices[:4])
        else:
            refs["ppr"] = pool.submit(ref_pagerank, csr_of(g_pr), src)
            deltas = analytic_phase(g_pr, g_s, src, refs, devices[:1])
            serving_phase(g_pr, g_s, sources, deltas, refs, devices[:1])
    log(f"all phases passed in {time.perf_counter() - t_all:.1f} s")
    if args.rehearse:
        log("rehearsal passed on the CPU; this is not a chip run")
        return 0
    result = {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
