"""Attention dispatch: pick the right kernel for the current mesh.

Under a multi-device mesh the attention runs as a shard_map island inside
the jitted step — Pallas kernels and ring collectives both need per-shard
(local) views, which GSPMD alone can't give them. On one device it's the
Pallas flash kernel or the XLA reference.

Only `impl="auto"` chooses: ring where the mesh shards `seq`, else the
flash kernel where the backend is a TPU and the lengths tile
(`flash_fits`), else the reference. An implementation asked for by name
runs or raises — `"flash"` never returns the reference (under a mesh it
means the kernel per shard), `"ring"` never skips the ring.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import flash_attention, flash_fits, mha_reference
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, causal: bool = True, impl: str = "auto"):
    """q[B,L,H,D], k/v[B,L,Hkv,D] — global (logical) shapes."""
    mesh = mesh_lib.current_mesh()
    multi = mesh is not None and mesh.size > 1
    seq_sharded = multi and mesh.shape[AXIS_SEQ] > 1
    B, L, H, D = q.shape
    use_flash = impl == "flash" or (
        impl == "auto" and _on_tpu() and flash_fits(L, k.shape[1]))
    if impl == "auto":
        if seq_sharded and L % mesh.shape[AXIS_SEQ] == 0:
            impl = "ring"
        elif multi and not seq_sharded:
            impl = "sharded_local"   # per-shard flash/ref under shard_map
        else:
            impl = "flash" if use_flash and not multi else "reference"
    elif impl == "flash" and multi:
        if seq_sharded:
            raise ValueError(
                "the flash kernel needs the whole sequence on each shard; "
                "a mesh that shards `seq` runs impl='ring' (or 'auto')")
        impl = "sharded_local"
    if impl in ("ring", "sharded_local"):
        if mesh is None:
            raise ValueError("sharded attention needs a mesh (use_mesh(...))")
        Hkv = k.shape[2]
        t = mesh.shape[AXIS_TENSOR]
        s = mesh.shape[AXIS_SEQ]
        bsz = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
        if impl == "ring" and L % s != 0:
            raise ValueError(
                f"ring attention needs L={L} divisible by the mesh's "
                f"seq axis ({s})")
        batch_ax = (AXIS_DATA, AXIS_FSDP) if B % bsz == 0 else None
        # heads shard over tensor only when q AND kv head counts divide it
        # (keeps the GQA repeat factor consistent per shard)
        head_ax = AXIS_TENSOR if (H % t == 0 and Hkv % t == 0) else None
        if impl == "ring":
            spec = P(batch_ax, AXIS_SEQ, head_ax, None)
            body = functools.partial(ring_attention, axis_name=AXIS_SEQ,
                                     causal=causal)
        else:
            # seq axis unsharded: each (batch, head) shard holds the full
            # sequence — run the flash kernel (or the reference, where
            # "auto" chose it) locally; pallas can't be auto-partitioned
            # by GSPMD, hence shard_map
            spec = P(batch_ax, None, head_ax, None)
            body = functools.partial(
                flash_attention if use_flash else mha_reference,
                causal=causal)
        fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
        return fn(q, k, v)
    if impl == "flash":
        return flash_attention(q, k, v, causal=causal)
    return mha_reference(q, k, v, causal=causal)
