"""A round's share of the HBM roofline for an ``(n, F)`` state, in percent.

The least time a round can take is :func:`bytes_per_round` of the unpadded
graph and F at the chip's peak HBM bandwidth (``bench/peaks.json``); the
share is that over the measured device time per round (``round_ms``).  F is
the ``columns`` attribute of the program's ``repro.solve`` records of the
traced solves; a program that does not record it reads nothing.
"""

from bench import scopes
from bench.metrics import round_ms
from bench.roofline import peak


def bytes_per_round(edges: int, vertices: int, columns: int) -> int:
    """HBM bytes one pull round of an F-wide state needs at the least.

    ``8·E + 4·F·E + 8·F·n``: per true directed edge a 4-byte source index,
    a 4-byte edge value and the F 4-byte source values it gathers; per
    vertex its F values read and written once.  At F = 1 this is
    ``bench.roofline.bytes_per_round``.
    """
    e, n, f = int(edges), int(vertices), int(columns)
    return 8 * e + 4 * f * e + 8 * f * n


def columns(run):
    """F of the traced solves, or ``None`` where the records do not say."""
    records = scopes.program_spans("repro.solve")
    if not records or not run.timed or len(records) < len(run.timed):
        return None
    found = {r.attrs.get("columns") for r in records[-len(run.timed):]}
    if len(found) != 1 or None in found:
        return None
    return found.pop()


def read(run):
    ms = round_ms.read(run)
    f = columns(run)
    if ms is None or f is None:
        return None
    least_s = bytes_per_round(run.edges, run.vertices, f) / peak(
        run.device_kind, "hbm_bytes_per_s"
    )
    return 100.0 * least_s / (ms / 1e3)
