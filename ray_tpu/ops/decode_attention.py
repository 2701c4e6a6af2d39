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

`latent_pool_decode_attention`, below the first, is a second kernel for a
pool of LATENTS (`models/latent_attention.py` `row_attention`): the two
share `block_of`'s rule and the index map that repeats a slot's last live
block, and nothing else.
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


# ------------------------------------------------------------------ latent
# A pool of LATENTS (models/latent_attention.py): a position keeps
# `[c ‖ k_r]`, W = R + Dr values that all H heads share, and the pool keeps
# the positions last, `[n_layers, B, W, M]`: no head axis, the positions in
# the lanes, ONE array that is key (all W rows) and value (the first R).
# The decode row comes ABSORBED, `qa [B, H, W]`, so a grid step is two
# products on one block `[W, block]`: the scores `[H, W] x [W, block]` and
# the probabilities against the block's first R rows, contracted over the
# lanes; the only mask is `position < lens[b]`. A slot that holds nothing
# is sent to the block the step before it fetched, so sixteen idle slots
# cost no read. (My chip run, PR 52, one layer of 16 slots of 18,432
# positions of 576, 64 heads, ms at 8 slots live of 6.8k-9.6k / all 16 of
# 6.4k-9.6k: `row_attention`'s XLA loop 0.464 / 0.463; this kernel at
# blocks of 512 / 1,024 / 2,048 0.200 / 0.154 / 0.148 and 0.331 / 0.273 /
# 0.274; the scores held transposed `[block, H]` and the accumulator
# `[R, H]` 0.231 / 0.223 / 0.223 and 0.390 / 0.411 / 0.423; masking a
# slot's last block alone no faster; idle slots fetching their own first
# block 0.172 for 0.154. A grid step that computes nothing costs 0.09 us.)
_LATENT_MAX_BLOCK = 2048
# two buffers of a block [576, 2048] are 4.7 MB in bf16 and 9.4 in float32,
# the float32 scores and probabilities 0.5 MB each
_LATENT_VMEM = 32 * 2 ** 20


def latent_block_of(M: int) -> int:
    """The positions a grid step of the latent kernel reads of a slot."""
    return block_of(M, _LATENT_MAX_BLOCK)


def latent_fits(M: int, H: int, W: int, R: int) -> bool:
    """Whether the compiled latent kernel takes a pool of M positions of W
    values, the first R of them the value, under H heads: a block of
    positions and the value whole lane tiles, the W rows and the heads
    whole sublane tiles of either type."""
    return (latent_block_of(M) % _LANES == 0 and R % _LANES == 0
            and 0 < R <= W and W % 16 == 0 and H % 8 == 0)


def _latent_kernel(layer_ref, lens_ref, slot_ref, last_ref, q_ref, lat_ref,
                   acc_ref, m_ref, l_ref, *, block: int, scale: float):
    """One block of one slot's latents into the slot's running softmax,
    which lives in the output blocks as `_kernel`'s does:
    `latent_attention.row_attention`'s `fold`, one slot of it."""
    b, i = pl.program_id(0), pl.program_id(1)
    R = acc_ref.shape[-1]

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * block < lens_ref[b])
    def _():
        q, rows = q_ref[...], lat_ref[...]
        both = jnp.promote_types(q.dtype, rows.dtype)
        s = jnp.dot(q.astype(both), rows.astype(both),
                    preferred_element_type=jnp.float32) * scale  # [H, block]
        ok = i * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1) < lens_ref[b]
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:R], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def latent_pool_decode_attention(qa, pool, layer, lens, R: int, scale: float,
                                 *, max_block: int = _LATENT_MAX_BLOCK,
                                 interpret: bool = False):
    """The absorbed rows qa [B, H, W] against layer `layer` (traced) of the
    pool [n_layers, B, W, M], slot b's positions below lens[b], the scores
    times `scale` (a Python number) and the first `R` of a position's W
    values its value -> the running softmax's (largest score [B, H], sum
    [B, H], weighted latents [B, H, R]), all float32 and not yet divided.
    Of each slot the blocks up to its last live one are read, of a slot
    that holds nothing none."""
    B, H, W = qa.shape
    M = pool.shape[3]
    if not latent_fits(M, H, W, R) or pool.shape[1:3] != (B, W):
        raise ValueError(
            f"the latent decode kernel takes one absorbed row a slot "
            f"against whole lane tiles of positions and of values: got qa "
            f"{qa.shape}, a pool {pool.shape}, values of {R}")
    block = block_of(M, max_block)
    lens = lens.astype(jnp.int32)
    # the slot whose block a step fetches, and that slot's last live block:
    # a slot's own, or where it holds nothing those of the nearest slot
    # below it that does (slot 0's first block where there is none)
    slot = jax.lax.cummax(jnp.where(lens > 0, jnp.arange(B, dtype=jnp.int32),
                                    0))
    last = jnp.maximum((lens[slot] + block - 1) // block - 1, 0)
    acc, m, l = _latent_call(B, M, H, W, R, block, float(scale), interpret)(
        jnp.reshape(layer, (1,)).astype(jnp.int32), lens, slot, last, qa,
        pool)
    return m[..., 0], l[..., 0], acc


@functools.lru_cache(maxsize=None)
def _latent_call(B: int, M: int, H: int, W: int, R: int, block: int,
                 scale: float, interpret: bool):
    """The latent kernel's `pallas_call` of one shape, built ONCE, so that
    a stack's layers share one traced kernel (ops/tile_attention.py
    `_call`)."""

    def lat_block(b, i, layer_ref, lens_ref, slot_ref, last_ref):
        # past the slot's last live block: that block again (no new DMA);
        # a slot that holds nothing: the block the step before fetched
        at = jnp.where(lens_ref[b] > 0, jnp.minimum(i, last_ref[b]),
                       last_ref[b])
        return layer_ref[0], slot_ref[b], 0, at

    def per_slot(b, i, layer_ref, lens_ref, slot_ref, last_ref):
        return b, 0, 0

    stat = jax.ShapeDtypeStruct((B, H, _LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_latent_kernel, block=block, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, M // block),
            in_specs=[
                pl.BlockSpec((None, H, W), per_slot),
                pl.BlockSpec((None, None, W, block), lat_block),
            ],
            out_specs=[
                pl.BlockSpec((None, H, R), per_slot),
                pl.BlockSpec((None, H, _LANES), per_slot),
                pl.BlockSpec((None, H, _LANES), per_slot),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, R), jnp.float32), stat, stat],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM),
        name="latent_pool_decode_attention",
        interpret=interpret,
    )
