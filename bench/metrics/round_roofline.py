"""A round's share of the HBM roofline, in percent.

The least time a round can take is ``bytes_per_round(E, n)`` (the unpadded
graph's bytes, ``bench.roofline``) at the chip's peak HBM bandwidth from
``bench/peaks.json``; the share is that over the measured device time per
round (``round_ms``).  A round moves far more bytes than it computes
operations, so HBM bandwidth is its bound.
"""

from bench.metrics import round_ms
from bench.roofline import bytes_per_round, peak


def read(run):
    ms = round_ms.read(run)
    if ms is None:
        return None
    least_s = bytes_per_round(run.edges, run.vertices) / peak(
        run.device_kind, "hbm_bytes_per_s"
    )
    return 100.0 * least_s / (ms / 1e3)
