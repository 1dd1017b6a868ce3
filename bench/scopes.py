"""Device time of the round by program scope, and host time in the program's spans.

The program names the three kinds of work of a commit step with
``jax.named_scope`` (``commit.gather``, ``commit.segment_reduce``,
``commit.publish``) and wraps each solve in the host span ``repro.solve``.
On a TPU the ``.xplane.pb`` op events carry no ``op_name`` (their name is
the HLO instruction's text, ``%fusion.25 = s32[...] fusion(...)``); the scope
path is the ``tf_op`` of the same op (``fusion.25``, at the same start) in the
``<host>.trace.json.gz`` the profiler writes beside it, so :func:`load` joins
the two.  Only leaf ops count: an
event that encloses another on its line (the solve's ``while``, the round's
``fori_loop``) would count its children's time twice.

A trace without these scopes or spans, as a program without them gives,
reduces to ``None`` readings and raises nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import json
import statistics
from pathlib import Path

from bench import trace_reduce as tr

SCOPES = ("commit.gather", "commit.segment_reduce", "commit.publish")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "repro.")
SOLVE_SPAN = "repro.solve"
JOIN_TOLERANCE_NS = 1000  # trace.json.gz times are microseconds, printed to ns


@dataclasses.dataclass
class Trace:
    devices: list  # per device: [(op name, start_ns, end_ns, scope)]
    spans: list  # host spans named bench.* or repro.*: [(name, start_ns, end_ns)]


def scope_of(tf_op: str | None) -> str:
    """The scope of ``SCOPES`` that is a component of an op's name path."""
    parts = (tf_op or "").rstrip(":").split("/")
    return next((s for s in SCOPES if s in parts), UNSCOPED)


def _json_ops(path: Path) -> dict:
    """``{device plane: {op name: [(start_ns, tf_op)] in time order}}``."""
    with gzip.open(path, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    planes, lines = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            lines[(e["pid"], e["tid"])] = e["args"]["name"]
    out = {}
    for e in events:
        if e.get("ph") != "X" or lines.get((e.get("pid"), e.get("tid"))) != tr.OPS_LINE:
            continue
        plane = planes.get(e["pid"], "")
        if not tr.DEVICE_PLANE.match(plane):
            continue
        start = round(float(e["ts"]) * 1000)
        out.setdefault(plane, {}).setdefault(e["name"], []).append(
            (start, (e.get("args") or {}).get("tf_op"))
        )
    for ops in out.values():
        for v in ops.values():
            v.sort(key=lambda c: c[0])
    return out


def short_name(name: str) -> str:
    """An op's instruction name: ``%fusion.25 = s32[8] fusion(...)`` -> ``fusion.25``."""
    return name.split(" ")[0].lstrip("%")


def _tf_op(candidates, start_ns):
    """The ``tf_op`` of the candidate that started nearest ``start_ns``."""
    if not candidates:
        return None
    i = bisect.bisect_left(candidates, start_ns, key=lambda c: c[0])
    t, op = min(candidates[max(i - 1, 0):i + 1], key=lambda c: abs(c[0] - start_ns))
    return op if abs(t - start_ns) <= JOIN_TOLERANCE_NS else None


def load(trace_dir) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``, with each op's scope."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    xplane = files[-1]
    beside = sorted(xplane.parent.glob("*.trace.json.gz"))
    named = _json_ops(beside[0]) if beside else {}
    data = ProfileData.from_file(str(xplane))
    devices, spans = [], []
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            tf_ops = named.get(plane.name, {})
            devices.append([
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                 scope_of(_tf_op(tf_ops.get(short_name(e.name)), int(e.start_ns))))
                for line in plane.lines
                if line.name == tr.OPS_LINE
                for e in line.events
            ])
        elif plane.name.startswith("/host:"):
            spans += [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for line in plane.lines
                for e in line.events
                if e.name.startswith(SPAN_PREFIXES)
            ]
    return Trace(devices=devices, spans=spans)


def leaves(ops) -> list:
    """The ops of one line that enclose no other op of that line."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [
        o for o, nxt in zip(ordered, ordered[1:] + [None])
        if nxt is None or nxt[1] >= o[2]
    ]


@dataclasses.dataclass
class Reduction:
    scope_s: dict  # scope (or "unscoped") -> leaf-op seconds in the solve spans
    solve_host_s: list  # per repro.solve span in the window: seconds no op ran
    idle_gaps: list  # [(innermost bench.*/repro.* span, seconds)], longest first

    @property
    def has_scopes(self) -> bool:
        return any(self.scope_s.get(s, 0) > 0 for s in SCOPES)


def reduce(trace: Trace, window="bench.window", solve="bench.solve", top=10) -> Reduction:
    """Scope totals, host time per program solve and idle gaps of ``window``."""
    (lo, hi) = [(s, e) for name, s, e in trace.spans if name == window][0]
    solves = [(s, e) for name, s, e in trace.spans if name == solve]
    n = len(trace.devices)
    totals = {s: 0.0 for s in (*SCOPES, UNSCOPED)}
    for ops in trace.devices:
        for _, s, e, scope in leaves(ops):
            for a, b in solves:
                d = min(e, b) - max(s, a)
                if d > 0:
                    totals[scope] += d / n / 1e9
    plain = [[(name, s, e) for name, s, e, _ in ops] for ops in trace.devices]
    host = [
        ((e - s) - sum(tr.busy_ns(ops, s, e) for ops in plain) / n) / 1e9
        for name, s, e in trace.spans
        if name == SOLVE_SPAN and lo <= s and e <= hi
    ]
    ops0 = plain[0]
    idle = sorted(
        ((tr.span_at(trace.spans, (s + e) // 2), (e - s) / 1e9)
         for s, e in tr.gaps(ops0, lo, hi)),
        key=lambda kv: -kv[1],
    )[:top]
    return Reduction(scope_s=totals, solve_host_s=host, idle_gaps=idle)


def _newest(root: Path):
    files = sorted(root.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int, rounds: int, round_ms: float) -> Reduction:
    from bench.harness import log

    red = reduce(load(Path(path).parent))
    per_round = {k: 1e3 * v / rounds for k, v in red.scope_s.items()}
    scoped = sum(per_round[s] for s in SCOPES)
    host = [round(1e3 * s, 3) for s in red.solve_host_s]
    log("scopes ms/round: " + ", ".join(f"{k} {v:.3f}" for k, v in per_round.items())
        + f"; scoped {100 * scoped / round_ms:.2f}% of round_ms {round_ms:.3f}"
        + f"; repro.solve host ms {host}"
        + "; idle gaps " + ", ".join(f"{k} {1e3 * v:.3f} ms" for k, v in red.idle_gaps))
    return red


def for_run(run):
    """The :class:`Reduction` of a traced run's newest trace, or ``None``."""
    from bench import harness

    if run.trace is None or not run.timed:
        return None
    path = _newest(Path(harness.TRACE_ROOT))
    if path is None:
        return None
    rounds = sum(s.rounds for s in run.timed)
    round_ms = 1e3 * run.trace.solve_busy_s / rounds
    return _reduced(str(path), path.stat().st_mtime_ns, rounds, round_ms)


def scope_ms(run, scope: str):
    """Device ms per round of leaf ops in ``scope`` inside the solve spans."""
    red = for_run(run)
    if red is None or not red.has_scopes:
        return None
    return 1e3 * red.scope_s[scope] / sum(s.rounds for s in run.timed)


def solve_host_ms(run):
    """Median over solves of the ms of a ``repro.solve`` span with no device op.

    The median, because the host's clock on a shared machine now and then
    stalls one solve for a tenth of a second (a wait for the runtime's
    completion flag, with the device done).
    """
    red = for_run(run)
    if red is None or not red.solve_host_s:
        return None
    return 1e3 * statistics.median(red.solve_host_s)


def program_spans(name: str):
    """The program's in-memory span records named ``name`` (``None`` without them)."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.records(name)
