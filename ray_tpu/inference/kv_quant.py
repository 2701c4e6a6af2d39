"""int8 KV-cache quantization (ROADMAP item 1: ~double the slot count
per HBM byte).

Scheme: symmetric per-row int8 over the head dimension — each
``[..., D]`` row of a K or V span quantizes independently with
``scale = amax / 127`` (a zero row keeps scale 1.0 so dequantize is
exact), stored as ``int8[..., D]`` values plus an ``fp32[...]`` scale
array that drops the last axis. Per-(position, head) scales are the
finest granularity that adds no matmul work on the read path: the
engine dequantizes a span in one fused multiply when it loads it back
into fp scratch, and attention itself never sees int8.

Where it plugs in (ray_tpu/inference/kv_cache.py ``BlockStore``, the only
importer; ``EngineConfig.kv_quant="int8"``):

- the prefix-cache blocks are stored as int8 + scales; the store's save
  encodes and its load decodes (still fixed-shape, still compile-once);
- the decode slot pool and prefill scratch stay full precision — the
  pool is donated through the one decode program and rewriting it as
  int8 would put a quantize/dequantize pair on the per-token hot path
  for zero capacity win (slots are transient; blocks are the cache);
- to keep greedy output bit-identical between a prefix-cache HIT and
  MISS, the miss path publishes each completed chunk and immediately
  reloads the dequantized values into its own scratch, so both paths
  attend over exactly the same (once-quantized) numbers;
- the disagg hand-off (serve/disagg.py) ships int8 spans + scales —
  the wire payload shrinks by ~``itemsize * D / (D + 4)``.

Cross-mode hand-offs: an fp exporter's span is quantized by the int8
importer on the device with this arithmetic under jit (a numpy mirror is
NOT bit-identical to the jitted form: a scale's last bit differs in about
one row of twenty on the CPU backend); an int8 span is dequantized for an
fp importer on the host.
"""

from __future__ import annotations

import numpy as np

VALID_MODES = ("none", "int8")


def check_mode(mode) -> str:
    mode = mode or "none"
    if mode not in VALID_MODES:
        raise ValueError(f"kv_quant={mode!r}; expected one of "
                         f"{VALID_MODES}")
    return mode


def quantize_kv(x):
    """jnp: fp[..., D] -> (int8[..., D], fp32 scale[...])."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype):
    """jnp inverse: (int8[..., D], fp32[...]) -> dtype[..., D]."""
    import jax.numpy as jnp
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def dequantize_kv_np(q, scale, dtype=np.float32):
    """Host mirror of :func:`dequantize_kv` (one multiply: the same
    bits), for an int8 span landing in an fp store."""
    return (np.asarray(q, np.float32)
            * np.asarray(scale, np.float32)[..., None]).astype(dtype)


def slot_gain(head_dim: int, fp_itemsize: int) -> float:
    """Capacity multiplier of int8 blocks vs ``fp_itemsize``-byte
    blocks at equal HBM: ``itemsize * D / (D + 4)`` (the +4 is the
    fp32 scale per row). ~1.94x for fp16 at D=128, ~3.88x for fp32."""
    return fp_itemsize * head_dim / float(head_dim + 4)
