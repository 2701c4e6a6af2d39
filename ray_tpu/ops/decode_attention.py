"""Decode attention over a slot pool, read where it lies.

`pool_decode_attention` is a Pallas TPU kernel for one query row a slot
against K and V pools `[n_layers, B, M, Hkv, D]`: it is handed the pools
whole, the layer's number, the slots' live lengths and an optional mask
over the positions, and reads of each slot only the key blocks that hold a
live position (a block past them is neither fetched, its index map
repeating the last live block, nor computed). It knows nothing of what
made the mask.

The pool's minor dimensions `(Hkv, D)` are taken as they lie: a layer of a
slot is read as the matrix `[M * Hkv, D]` whose row `m * Hkv + h` is
position m of KV head h (the same bytes; D must be one lane tile, 128, for
the reshape to be free on the TPU). All H query heads meet all rows in one
matmul a block, `[H, D] x [block * Hkv, D]^T`, and a score counts only
where the row's KV head is the query head's: the mask operand carries, a
row of that matrix, the KV head it belongs to where its position is
selected and -1 where it is not, so one compare decides both. Scores,
softmax statistics and the accumulator are float32, probabilities and
values bf16 (the pools' type), as `models/sparse_attention.py` has them.

The kernel returns the running softmax UNNORMALISED (largest score, sum,
weighted values) so that the caller folds further keys in (a decode row's
own) before dividing. Off the TPU it only runs with `interpret=True`; the
choice between this kernel and the XLA form belongs to the caller
(`models/sparse_attention.py` `sparse_decode_attention`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
# positions a grid step reads of a slot: 512 against 1024 reads 0.2397
# against 0.2525 ms a layer at 8 slots of 4.2k-8.5k live, 0.1894 against
# 0.1803 at 4k each (my chip run, PR 41): a slot's last block is half as
# wasteful, the grid twice as long
_MAX_BLOCK = 512


def block_of(m: int, max_block: int = _MAX_BLOCK) -> int:
    """The largest power of two up to `max_block` that divides M."""
    b = max_block
    while m % b:
        b //= 2
    return b


def fits(M: int, Hkv: int, D: int) -> bool:
    """Whether the compiled kernel takes these pools: D one lane tile, and
    a block of rows a whole number of lane tiles."""
    return D == _LANES and (block_of(M) * Hkv) % _LANES == 0


def _kernel(layer_ref, lens_ref, q_ref, k_ref, v_ref, code_ref,
            acc_ref, m_ref, l_ref, *, block: int, group: int, scale: float):
    """One key block of one slot into the slot's running softmax, which
    lives in the output blocks: they keep their place in VMEM over the
    slot's grid steps and are written back once, after its last."""
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * block < lens_ref[b])
    def _():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # row r of the block is KV head code[r] (or -1: not selected);
        # query head h reads KV head h // group
        of_q = (jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
                // group).astype(jnp.float32)
        ok = code_ref[...] == of_q                          # [H, rows]
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _rows_of(k_pool, v_pool, lens, mask):
    """The pools as matrices [n_layers, B, M * Hkv, D] and, a row of
    them, the KV head it belongs to where its position is live and
    selected, else -1: [B, M * Hkv] float32. A position's flag is spread
    over its Hkv rows by a matmul, `c` positions at a time against a
    constant [c, c * Hkv]: repeating it along the lanes relays the mask
    through a minor dimension of Hkv (0.10 ms a layer at 8 x 17,408
    against 0.01; my chip run, PR 41)."""
    n_layers, B, M, Hkv, D = k_pool.shape
    live = jnp.arange(M)[None, :] < lens[:, None]
    mask = live if mask is None else mask & live
    c = block_of(M, _LANES)
    row = jnp.arange(c * Hkv)
    spread = (row[None, :] // Hkv == jnp.arange(c)[:, None]) \
        * (row[None, :] % Hkv + 1)                          # [c, c * Hkv]
    code = jnp.einsum("bgm,mn->bgn", mask.reshape(B, M // c, c).astype(
        jnp.float32), spread.astype(jnp.float32)).reshape(B, M * Hkv) - 1.0
    return (k_pool.reshape(n_layers, B, M * Hkv, D),
            v_pool.reshape(n_layers, B, M * Hkv, D), code)


def pool_decode_reference(q, k_pool, v_pool, layer, lens, mask=None, *,
                          max_block: int = _MAX_BLOCK):
    """`pool_decode_attention` in XLA, the kernel's arithmetic step for
    step: a loop over the key blocks up to the LONGEST live slot's last,
    every slot's block read in each step."""
    B, H, D = q.shape
    M, Hkv = k_pool.shape[2:4]
    block = block_of(M, max_block)
    rows = block * Hkv
    kf, vf, code = _rows_of(k_pool, v_pool, lens, mask)
    of_q = (jnp.arange(H) // (H // Hkv)).astype(jnp.float32)[None, :, None]

    def step(i, carry):
        m, l, acc = carry
        kb, vb = (jax.lax.dynamic_slice(
            p, (layer, 0, i * rows, 0), (1, B, rows, D))[0] for p in (kf, vf))
        ok = jax.lax.dynamic_slice_in_dim(
            code, i * rows, rows, 1)[:, None, :] == of_q
        s = jnp.einsum("bhd,bnd->bhn", q, kb,
                       preferred_element_type=jnp.float32) * D ** -0.5
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1),
                acc * alpha[..., None] + jnp.einsum(
                    "bhn,bnd->bhd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32))

    return jax.lax.fori_loop(
        0, (jnp.max(lens) + block - 1) // block, step,
        (jnp.full((B, H), NEG_INF, jnp.float32),
         jnp.zeros((B, H), jnp.float32), jnp.zeros((B, H, D), jnp.float32)))


def pool_decode_attention(q, k_pool, v_pool, layer, lens, mask=None, *,
                          max_block: int = _MAX_BLOCK,
                          interpret: bool = False):
    """q [B, H, D] against layer `layer` (traced) of the pools
    [n_layers, B, M, Hkv, D], slot b's positions below lens[b] under
    `mask` [B, M] bool (absent: every live position) -> the running
    softmax's (largest score [B, H], sum [B, H], weighted values
    [B, H, D]), all float32 and not yet divided."""
    B, H, D = q.shape
    M, Hkv = k_pool.shape[2:4]
    block = block_of(M, max_block)
    rows = block * Hkv
    kf, vf, code = _rows_of(k_pool, v_pool, lens, mask)
    code = code[:, None, :]

    def kv_block(b, i, layer_ref, lens_ref):
        # past the slot's last live block: that block again (no new DMA)
        last = jnp.maximum((lens_ref[b] + block - 1) // block - 1, 0)
        return layer_ref[0], b, jnp.minimum(i, last), 0

    def code_block(b, i, layer_ref, lens_ref):
        return b, 0, kv_block(b, i, layer_ref, lens_ref)[2]

    def per_slot(b, i, layer_ref, lens_ref):
        return b, 0, 0

    stat = jax.ShapeDtypeStruct((B, H, _LANES), jnp.float32)
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, block=block, group=H // Hkv,
                          scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, M // block),
            in_specs=[
                pl.BlockSpec((None, H, D), per_slot),
                pl.BlockSpec((None, None, rows, D), kv_block),
                pl.BlockSpec((None, None, rows, D), kv_block),
                pl.BlockSpec((None, 1, rows), code_block),
            ],
            out_specs=[
                pl.BlockSpec((None, H, D), per_slot),
                pl.BlockSpec((None, H, _LANES), per_slot),
                pl.BlockSpec((None, H, _LANES), per_slot),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, D), jnp.float32), stat, stat],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="pool_decode_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      q, kf, vf, code)
    return m[..., 0], l[..., 0], acc
