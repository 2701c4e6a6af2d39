"""The yardstick: the chips' peaks, percentiles, spreads.

What a utilisation or a roofline share is divided by lives under the
benchmark's own directories, so that a later PR can change the program and
not the ruler: the peaks here, a model's operation and byte counts in its
family's file (families/<family>.py).
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence

# Peak rates of one chip, keyed by JAX's `device_kind`, exact match.
# Source: Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s in bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM per chip.
# No CPU row and no environment override (util/profiling.py has both): a
# device that is not in the table is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/yardstick.py "
            f"PEAKS: add its published rates, with their source") from None


# ------------------------------------------------------------ statistics
def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    order statistics (numpy's default). Raises on an empty list: a metric
    with no sample is left out, not reported as 0."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver computes it (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
