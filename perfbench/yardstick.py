"""The yardstick: peaks, operation and byte counts, percentiles, spreads.

Everything a utilisation or a roofline share is computed from lives here,
under the benchmark's own directory, so that a later PR can change the
program and not the ruler. Copied from the program where it had sound
arithmetic (origins named at each function; the originals are listed in
PERF.md's Open questions for a later PR to delete).
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence

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


# ------------------------------------------------------- model arithmetic
# `m` below is the configuration file's dict (the model's published keys).
def _attn_params(m: dict) -> int:
    d, h, kv = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd = d // h
    return d * h * hd * 2 + d * kv * hd * 2          # q, o and k, v


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]   # gate, up, down


def n_experts(m: dict) -> int:
    return int(m.get("num_local_experts", 0) or 0)


def layer_params(m: dict, active_only: bool) -> int:
    """Matmul parameters of one layer. With experts: all of them (what is
    stored and streamed) or the `num_experts_per_tok` a token uses (what
    does useful work), plus the router."""
    e = n_experts(m)
    if e == 0:
        return _attn_params(m) + _expert_params(m)
    k = m["num_experts_per_tok"] if active_only else e
    return _attn_params(m) + k * _expert_params(m) + m["hidden_size"] * e


def stored_param_bytes(m: dict, param_bytes: float) -> float:
    """Bytes of the weights as stored on the device: every layer with all
    its experts, the embedding and the unembedding (untied). The norms'
    scales and the router (fp32) are below a thousandth and left out."""
    n = m["num_hidden_layers"] * layer_params(m, active_only=False)
    n += 2 * m["vocab_size"] * m["hidden_size"]
    return n * param_bytes


def causal_attention_flops(m: dict, batch: int, length: int,
                           backward: bool) -> float:
    """QK^T and AV over the causal half: 2 matmuls x 2 FLOP x B x L^2/2 x
    (heads x head_dim) a layer forward; the backward is twice the forward
    (recomputation inside the flash backward kernel does not count)."""
    d_attn = m["hidden_size"]           # heads x head_dim
    fwd = m["num_hidden_layers"] * 4.0 * batch * length * length \
        * d_attn / 2.0
    return fwd * (3.0 if backward else 1.0)


def train_step_flops(m: dict, batch: int, length: int) -> float:
    """Useful forward + backward FLOPs of one training step: 6 per matmul
    parameter a token (2 forward, 4 backward) plus causal attention.
    Origin: reports/mfu_ablate.py:train_step_flops, extended with the MoE
    case: only the experts a token is routed to do useful work.
    Recomputed (remat) operations do not count; the embedding lookup is
    not a matmul and does not count; the unembedding does."""
    n = m["num_hidden_layers"] * layer_params(m, active_only=True)
    n += m["hidden_size"] * m["vocab_size"]
    return 6.0 * n * batch * length \
        + causal_attention_flops(m, batch, length, backward=True)


def decode_step_bytes(m: dict, live_lens: Iterable[float],
                      param_bytes: float, kv_bytes: float) -> float:
    """Bytes one decode step must read: the weights as stored (every
    expert: at 16 rows x top-2 over 8 experts nearly all are touched, and
    the program's dense dispatch reads all regardless) and every live
    slot's K and V. The embedding table is read by rows, so only the
    unembedding half of the two tables counts.
    Origin: util/profiling.py:decode_step_bytes."""
    hd = m["hidden_size"] // m["num_attention_heads"]
    w = stored_param_bytes(m, param_bytes) \
        - m["vocab_size"] * m["hidden_size"] * param_bytes
    kv = sum(2.0 * m["num_hidden_layers"] * float(n)
             * m["num_key_value_heads"] * hd * kv_bytes for n in live_lens)
    return w + kv


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
