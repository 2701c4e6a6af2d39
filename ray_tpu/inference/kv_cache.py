"""The KV cache's storage: each array has one owner, each format one
description.

The LAYOUT ``[n_layers, rows, length, Hkv, D]`` is the model's
(``models/transformer.py``: ``kv_cache_shape``, ``kv_cache_sharding``,
and for a model with a sparse-attention indexer ``index_cache_shape``, a
third cache of one head of indexer keys a position; ``cache_shapes``
names them all; the cached forward is what reads them). This module owns
what the engine keeps in that layout and nothing else knows how:

- ``SlotPool``: every pool of one model's decode slots (K and V; an
  indexer's keys; for a model with layers of several kinds the pooled
  keys of its block-sparse layers and the float32 states of its linear
  layers, each over its own kind's layers; for layers that run attention
  heads and a state-space mixer side by side K and V AND two float32
  states, the mixer's and its convolution's tail; for sliding-window
  layers beside full-attention ones K and V in RINGS beside K and V by
  position; for latent attention ONE pool of latent + rope key a position
  with no head axis and the positions last), the scratch a prompt
  prefills into, and the program that
  makes a finished scratch a slot. Pools are of three natures
  (``CACHE_POS_AXIS``, ``CACHE_RINGS``): with a position axis, of which a
  slot takes the scratch's first ``slot_len`` positions; without (a
  state), of which it takes the scratch's whole entry, so a slot never
  inherits its last owner's; and a ring (``wk``, ``wv``): a position
  axis of ``win_ring`` places whatever ``length`` is, position p at
  p mod ``win_ring``, in a slot and in a scratch alike, so a slot takes
  the scratch's ring whole too (the places a short prompt never wrote
  hold the fresh scratch's zeros, and the attention masks every place
  whose position is not in the row's window).
- ``BlockStore``: the prefix cache's blocks. A block's FORMAT is the
  tuple of arrays that hold it, which is also its wire form between
  replicas: ``"none"`` = (k, v) in the cache dtype; ``"int8"`` =
  (k, v, k_scales, v_scales), int8 values + an fp32 scale per
  (position, head) (kv_quant.py). Four programs, written once over that
  tuple: save (scratch -> block, encode), load (block -> scratch,
  decode), export (block -> span), import (span -> block). Every one has
  a fixed span shape and traced offsets: one compile, ever.

The slot pools and scratches stay full precision whatever the blocks'
format: a pool is donated through the one decode program, and int8 there
would put a quantize/dequantize pair on the per-token path.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np

from ray_tpu.inference import kv_quant

check_format = kv_quant.check_mode


@functools.lru_cache(maxsize=None)
def _sharded_zeros(sharding):
    """Jitted zeros with an explicit output sharding, memoized per
    sharding (jit caches per (shape, dtype) static args underneath).
    Allocating through jit is what makes the result a GLOBAL array when
    the mesh spans multiple processes — a host-side ``jnp.zeros`` +
    ``device_put`` only ever produces a single-process value."""
    import jax
    import jax.numpy as jnp
    return jax.jit(jnp.zeros, static_argnums=(0, 1),
                   out_shardings=sharding)


def zeros(shape, dtype, sharding=None):
    """Zeros with the sharding given (None: no mesh, a plain array)."""
    import jax.numpy as jnp
    if sharding is None:
        return jnp.zeros(shape, dtype)
    return _sharded_zeros(sharding)(tuple(shape), jnp.dtype(dtype))


def replicated(mesh):
    """The sharding of whatever is not a slot pool (None off a mesh)."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


class SlotPool:
    """``k``, ``v``: ``[n_layers, n_slots, length, Hkv, D]`` of one
    model, and ``ki`` ``[n_layers, n_slots, 1, DI, length]`` where the
    model has an indexer (None where not), or whatever else
    ``cache_shapes`` names (each an attribute of its name, in the type
    ``cache_dtype`` gives it), sharded as the layout says
    (pruned against THIS model's shape: a single KV head does not
    divide the tensor axis). The step programs take them donated, as the
    tuple ``pools()``, and the engine ``rebind``s them after each call.
    ``scratch`` holds the same tuple of one row of ``scratch_len``
    positions, replicated, for each prompt still prefilling, by request
    id."""

    def __init__(self, mcfg, n_slots: int, length: int, slot_len: int,
                 scratch_len: int, dtype, mesh=None, rules=None):
        import jax

        from ray_tpu.models.transformer import (cache_dtype, cache_shapes,
                                                kv_cache_sharding)
        self.dtype = dtype
        self.shapes = cache_shapes(mcfg, n_slots, length)
        self.scratch_shapes = cache_shapes(mcfg, 1, scratch_len)
        self.dtypes = {n: cache_dtype(n, dtype) for n in self.shapes}
        self._scratch_sharding = replicated(mesh)
        self.k = self.v = self.ki = None
        self.rebind(tuple(
            zeros(shape, self.dtypes[name],
                  kv_cache_sharding(shape, mesh, rules, name)
                  if mesh is not None else None)
            for name, shape in self.shapes.items()))
        self.scratch: Dict[int, Tuple[Any, ...]] = {}
        # what a slot takes of a scratch: its first slot_len positions
        # (the scratch carries the largest tile of padding tail), or, of
        # a pool that has no position or is a ring, its whole entry
        taken = cache_shapes(mcfg, 1, slot_len)

        def insert(pools, scratch, slot):
            return tuple(
                jax.lax.dynamic_update_slice(
                    p, jax.lax.slice(s, (0,) * s.ndim, taken[name]),
                    (0, slot) + (0,) * (s.ndim - 2))
                for name, p, s in zip(self.shapes, pools, scratch))

        self._insert_fn = jax.jit(insert, donate_argnums=(0,))

    def pools(self) -> Tuple[Any, ...]:
        """(k, v), (k, v, ki), (k, v, kp, s), (k, v, s, c), (k, v, wk, wv)
        or (lat,): the order of ``cache_shapes``."""
        return tuple(getattr(self, n) for n in self.shapes)

    def rebind(self, pools) -> None:
        for name, pool in zip(self.shapes, pools):
            setattr(self, name, pool)

    def nbytes(self, names=None) -> int:
        """Bytes of the slots' pools (not of the scratches): of all, or of
        those named."""
        return sum(int(np.prod(shape)) * np.dtype(self.dtypes[n]).itemsize
                   for n, shape in self.shapes.items()
                   if names is None or n in names)

    def new_scratch(self):
        return tuple(zeros(shape, self.dtypes[n], self._scratch_sharding)
                     for n, shape in self.scratch_shapes.items())

    def insert(self, scratch, slot):
        """A finished prompt's scratch becomes slot ``slot``: an int, or
        an int32 scalar already on the device (the engine's, which its
        tile program hands back: the copy then waits for no transfer)."""
        if isinstance(slot, int):
            slot = np.int32(slot)
        self.rebind(self._insert_fn(self.pools(), tuple(scratch), slot))


def span_format(span) -> str:
    """The format of a host span (the tuple one block exports)."""
    return "int8" if len(span) == 4 else "none"


def writes_through(fmt: str) -> bool:
    """Whether a store of this format is written through chunk by chunk.
    An int8 block is not what was computed, so a miss writes each
    finished chunk through the store and attends what a later hit will
    load: hit and miss stay bit-identical."""
    return fmt == "int8"


def format_stats(fmt: str, head_dim: int, fp_itemsize: int) -> Dict:
    """What ``engine.stats()`` says of the blocks' format (int8 only)."""
    if fmt != "int8":
        return {}
    return {"kv_quant": "int8",
            "kv_quant_slot_gain": round(
                kv_quant.slot_gain(head_dim, fp_itemsize), 3),
            "kv_quant_slot_gain_vs_fp16": round(
                kv_quant.slot_gain(head_dim, 2), 3)}


class BlockStore:
    """``n_rows`` rows of a slot's shape, cut into ``chunk``-long blocks:
    block ``b`` is row ``b // blocks_per_row`` at offset
    ``(b % blocks_per_row) * chunk``. Replicated on a mesh: blocks are
    copied into the replicated scratch, never attended over in place."""

    def __init__(self, mcfg, n_rows: int, row_len: int, chunk: int,
                 dtype, fmt: str = "none", mesh=None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import kv_cache_shape
        self.format = check_format(fmt)
        self.chunk = chunk
        self.blocks_per_row = row_len // chunk
        self.n_blocks = n_rows * self.blocks_per_row
        int8 = self.format == "int8"
        shape = kv_cache_shape(mcfg, n_rows, row_len)
        span = kv_cache_shape(mcfg, 1, chunk)
        sh = replicated(mesh)
        vals = jnp.int8 if int8 else dtype
        self.arrays = (zeros(shape, vals, sh), zeros(shape, vals, sh))
        if int8:
            self.arrays += (zeros(shape[:-1], jnp.float32, sh),
                            zeros(shape[:-1], jnp.float32, sh))

        def encode(ck, cv):
            if not int8:
                return ck, cv
            (qk, ks), (qv, vs) = (kv_quant.quantize_kv(ck),
                                  kv_quant.quantize_kv(cv))
            return qk, qv, ks, vs

        def decode(parts):
            if not int8:
                return parts
            qk, qv, ks, vs = parts
            return (kv_quant.dequantize_kv(qk, ks, dtype),
                    kv_quant.dequantize_kv(qv, vs, dtype))

        def at(a, row, off):        # a values array or its scale rows
            return (0, row, off) + (0,) * (a.ndim - 3)

        def cut(arrays, row, off):
            return tuple(jax.lax.dynamic_slice(
                a, at(a, row, off), span[:a.ndim]) for a in arrays)

        def put(arrays, parts, row, off):
            return tuple(jax.lax.dynamic_update_slice(
                a, p, at(a, row, off)) for a, p in zip(arrays, parts))

        def save(arrays, sk, sv, row, dst, src):
            ck = jax.lax.dynamic_slice(sk, (0, 0, src, 0, 0), span)
            cv = jax.lax.dynamic_slice(sv, (0, 0, src, 0, 0), span)
            return put(arrays, encode(ck, cv), row, dst)

        def load(sk, sv, arrays, row, src, dst):
            ck, cv = decode(cut(arrays, row, src))
            sk = jax.lax.dynamic_update_slice(sk, ck, (0, 0, dst, 0, 0))
            sv = jax.lax.dynamic_update_slice(sv, cv, (0, 0, dst, 0, 0))
            return sk, sv

        self._save_fn = jax.jit(save, donate_argnums=(0,))
        self._load_fn = jax.jit(load, donate_argnums=(0, 1))
        self._export_fn = jax.jit(cut)
        self._import_fn = jax.jit(put, donate_argnums=(0,))
        self._encode_fn = jax.jit(encode)   # the wire bridge's, below

    def _address(self, block: int):
        row, boff = divmod(block, self.blocks_per_row)
        return np.int32(row), np.int32(boff * self.chunk)

    def save(self, scratch, block: int, src: int):
        """scratch[src : src + chunk] -> block (encoded)."""
        self.arrays = self._save_fn(self.arrays, *scratch,
                                    *self._address(block), np.int32(src))

    def load(self, scratch, block: int, dst: int):
        """block (decoded) -> scratch[dst : dst + chunk]; returns the
        scratch. A suffix prefill attends it as if just computed."""
        return self._load_fn(*scratch, self.arrays,
                             *self._address(block), np.int32(dst))

    def export(self, block: int) -> Tuple[np.ndarray, ...]:
        """The block as a host span in this store's format."""
        parts = self._export_fn(self.arrays, *self._address(block))
        return tuple(np.asarray(p) for p in parts)

    def imports_exactly(self, span) -> bool:
        """Whether ``import_span`` lands this span as the numbers a local
        prefill would have saved here. int8 into fp is the one lossy
        direction (dequantized values are not the fp-prefilled ones); fp
        into int8 quantizes with the save path's arithmetic."""
        return not (span_format(span) == "int8" and self.format == "none")

    def import_span(self, span, block: int):
        """A host span in either format -> block. A span in the other
        format is bridged: fp encoded on the device by ``save``'s own
        arithmetic (a numpy mirror differs from the jitted quantizer in a
        scale's last bit, about one row in twenty on the CPU backend),
        int8 dequantized on the host (``imports_exactly``)."""
        import jax.numpy as jnp
        if span_format(span) != self.format:
            if self.format == "int8":
                span = self._encode_fn(jnp.asarray(span[0]),
                                       jnp.asarray(span[1]))
            else:
                span = (kv_quant.dequantize_kv_np(span[0], span[2]),
                        kv_quant.dequantize_kv_np(span[1], span[3]))
        parts = tuple(jnp.asarray(p, a.dtype)
                      for p, a in zip(span, self.arrays))
        self.arrays = self._import_fn(self.arrays, parts,
                                      *self._address(block))
