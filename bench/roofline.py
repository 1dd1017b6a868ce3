"""The work a round must do, and the chip's peaks to hold it against.

The bytes are counted from the graph, never from the program's schedule, so
padding, stripe layouts and kernels all read the same yardstick.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def bytes_per_round(edges: int, vertices: int) -> int:
    """HBM bytes one pull round needs at the least: ``12·E + 8·n``.

    Per true directed edge a 4-byte source index, a 4-byte edge value and
    the 4-byte source value it gathers; per vertex its value read and
    written once.
    """
    return 12 * int(edges) + 8 * int(vertices)


def peak(device_kind: str, key: str) -> float:
    """``key`` of ``device_kind`` in ``peaks.json``; a missing kind is an error."""
    devices = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name} "
            f"(known: {sorted(devices)})"
        )
    return float(devices[device_kind][key])
