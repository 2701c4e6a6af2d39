"""Attention of a prefill tile against its scratch, the key blocks read
where they lie.

`tile_attention` is a Pallas TPU forward kernel for a tile `q [1, S, H, D]`
at absolute positions `pos0 .. pos0 + S - 1` (pos0 one traced scalar)
against one layer of a request's scratch `[1, M, Hkv, D]` that already
holds the tile's own rows: a cache by position (`window == 0`, position p
at p) or a RING of M places (position p at p mod M) of which a row attends
the `window` newest positions up to its own. It is
`models/transformer.py` `_tile_attention`'s running softmax, step for
step, with the statistics and the float32 accumulator of a block of query
rows held in VMEM over that block's key steps instead of carried through
HBM every block.

The walk. The grid is (KV head, block of `bq` query rows, key step). A
block of query rows meets the blocks of `kb` POSITIONS from its first
row's oldest key to its last row's own (`block_walk`: first block and
count, scalar-prefetched); step i reads block `first + i` at its place in
the scratch (`(c % (M // kb)) * kb` in a ring), and a step past the count
repeats the last block (no new DMA) and computes nothing. A block wholly
above the diagonal or wholly out of the window is thus neither read nor
multiplied. The mask is arithmetic on positions (`kpos <= qpos`, and
`kpos > qpos - window`), built only in the blocks a diagonal crosses.

The layout. A KV head's keys are the columns `h * D .. (h + 1) * D` of
the matrix `[M, Hkv * D]`, and a block `[kb, D]` of them is one strided
DMA, so K and V are read once a KV head and never repeated for its query
heads. (The bytes of `[M, Hkv, D]` in order; on the TPU, where that array
is tiled over `(Hkv, D)`, XLA makes the matrix by one relayout of the
layer's K and V before the call: the caller writes the tile's own rows
into the matrix, not into the scratch first, `_tile_attention`.) The
G = H / Hkv query heads of a KV head are the lane tiles of one
`[bq, G * D]` block of `q` as `[S, H * D]`; they meet the key block one
after another out of VMEM. Scores and the
accumulator are held transposed (`[kb, bq]`, `[D, bq]`), as in
`ops/attention.py`: a row's running max and sum are then one lane each.

Off the TPU the kernel only runs with `interpret=True`; the choice between
it and the XLA loop belongs to the caller (`models/sparse_attention.py`
`_tile_kernel_takes`).

`latent_tile_attention`, at the end of the file, is a second kernel for a
layer that caches LATENTS and no K and V (`models/latent_attention.py`): a
different body that shares the walk (`blocks_of`, `block_walk`,
`max_steps`) and nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _dot, _dot_nt, _dot_tn
from ray_tpu.ops.decode_attention import block_of

NEG_INF = -1e30
_LANES = 128
# query rows a grid step holds of each of a KV head's G heads: aligned
# to the key blocks, a block of 512 rows under a window of 4,096 meets
# nine key blocks of 512 (eight would hold its window); 1,024 rows meet
# ten, 256 the same nine at twice the steps and twice the reads of K and V
_MAX_ROWS = 512
# G * bq, the columns of a step's accumulators: [D, 4096] float32 is 2 MB
_MAX_COLS = 4096


def blocks_of(S: int, M: int, G: int, max_rows: int = _MAX_ROWS):
    """(query rows, key positions) of the kernel's blocks: the key block is
    the XLA loop's (`decode_attention.block_of`), so both forms fold the
    same keys in the same order."""
    bq = block_of(S, max_rows)
    while bq > _LANES and G * bq > _MAX_COLS:
        bq //= 2
    return bq, block_of(M)


def fits(S: int, M: int, H: int, Hkv: int, D: int, window: int = 0) -> bool:
    """Whether the compiled kernel takes a tile of S rows against M places:
    D one lane tile, the tile and the scratch whole blocks of whole lane
    tiles, a ring that holds what the tile attends."""
    if D != _LANES or H % Hkv or S % _LANES:
        return False
    bq, kb = blocks_of(S, M, H // Hkv)
    return (kb % _LANES == 0 and (H // Hkv) * bq <= _MAX_COLS
            and not (window and S + window - 1 > M))


def block_walk(pos0, S: int, M: int, window: int, bq: int, kb: int):
    """(first, count), each [S // bq] int32: the blocks of `kb` positions
    the j-th block of `bq` query rows meets, from its first row's oldest
    key to its last row's own (a cache by position holds no block past
    its M // kb). Integer arithmetic on `pos0`, traced or not."""
    q0 = pos0 + jnp.arange(S // bq, dtype=jnp.int32) * bq
    last = (q0 + bq - 1) // kb
    if window:
        first = jnp.maximum(q0 - window + 1, 0) // kb
    else:
        first = jnp.zeros_like(q0)
        last = jnp.minimum(last, M // kb - 1)
    return first, last - first + 1


def max_steps(M: int, window: int, bq: int, kb: int) -> int:
    """The most key blocks a block of query rows can meet (the grid's
    last dimension): without a window, every block of the cache; with
    one, what bq + window - 1 positions can cross, which may be one more
    than the ring has places (the first and the last then share a place,
    which holds of each the positions the other's rows do not see)."""
    if not window:
        return M // kb
    return (bq + window - 2) // kb + 2


def _kernel(pos_ref, first_ref, count_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, bq: int, kb: int, group: int,
            window: int, scale: float):
    """One key block into the running softmax of one block of query rows
    of one KV head's `group` query heads. `_softmax_step`'s arithmetic:
    the operands' product in float32 times `scale`, float32 statistics,
    the probabilities in the values' type into a float32 accumulator."""
    j, i = pl.program_id(1), pl.program_id(2)
    D = k_ref.shape[-1]

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = pos_ref[0] + j * bq                # the block's first row's
    k0 = (first_ref[j] + i) * kb            # the key block's first position
    # every pair visible: the block's last key is not past the first row
    # and its first is inside the last row's window
    whole = k0 + kb - 1 <= q0
    if window:
        whole &= k0 > q0 + bq - 1 - window

    def fold(masked: bool):
        k, v = k_ref[...], v_ref[...]
        if masked:
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (kb, bq), 0)
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (kb, bq), 1)
            ok = kpos <= qpos
            if window:
                ok &= kpos > qpos - window
        for g in range(group):
            s = _dot_nt(k, q_ref[:, g * D:(g + 1) * D]) * scale   # [kb, bq]
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            m = m_ref[g]
            m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(ok, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=0, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + _dot_tn(v, p.astype(v.dtype))
            m_ref[g] = m_new

    live = i < count_ref[j]
    pl.when(live & whole)(lambda: fold(False))
    pl.when(live & jnp.logical_not(whole))(lambda: fold(True))

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        for g in range(group):
            out = acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)
            o_ref[:, g * D:(g + 1) * D] = out.T.astype(o_ref.dtype)


def tile_attention(q, k_cache, v_cache, pos0, window: int = 0, *,
                   max_rows: int = _MAX_ROWS, interpret: bool = False):
    """q [1, S, H, D] at positions pos0 + 0..S-1 against k_cache, v_cache
    [1, M, Hkv, D] (or, the same bytes in order, [1, M, Hkv * D]) that
    hold the tile's own rows (rings where `window`) -> [1, S, H, D] in q's
    type."""
    _, S, H, D = q.shape
    M = k_cache.shape[1]
    Hkv = k_cache.size // (k_cache.shape[0] * M * D)
    G = H // Hkv
    if not fits(S, M, H, Hkv, D, window) or q.shape[0] != 1 \
            or jnp.ndim(pos0):
        raise ValueError(
            f"the tile kernel takes one tile of whole {_LANES}-row blocks "
            f"at one start against whole key blocks, D {_LANES}: got q "
            f"{q.shape}, a scratch {k_cache.shape}, window {window}")
    bq, kb = blocks_of(S, M, G, max_rows)
    pos0 = jnp.asarray(pos0, jnp.int32)
    first, count = block_walk(pos0, S, M, window, bq, kb)
    out = _call(S, M, H, Hkv, D, window, bq, kb, jnp.dtype(q.dtype),
                interpret)(
        jnp.reshape(pos0, (1,)), first, count, q.reshape(S, H * D),
        k_cache.reshape(M, Hkv * D), v_cache.reshape(M, Hkv * D))
    return out.reshape(1, S, H, D)


@functools.lru_cache(maxsize=None)
def _call(S: int, M: int, H: int, Hkv: int, D: int, window: int, bq: int,
          kb: int, dtype, interpret: bool):
    """The `pallas_call` of one shape of tile and scratch, built ONCE: a
    stack's layers of one kind then share one traced kernel (`pallas_call`
    hands back a jitted function, and a new one every time it is built:
    traced anew a layer, the unrolled body cost a second of a start a
    layer on the chip's host, five in Trinity's tile program; my chip
    run, PR 48)."""
    G, places = H // Hkv, M // kb

    def q_block(h, j, i, pos_ref, first_ref, count_ref):
        return j, h

    def kv_block(h, j, i, pos_ref, first_ref, count_ref):
        # past the rows' last block: that block again (no new DMA)
        c = first_ref[j] + jnp.minimum(i, count_ref[j] - 1)
        return (c % places if window else c), h

    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, kb=kb, group=G, window=window,
                          scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Hkv, S // bq, max_steps(M, window, bq, kb)),
            in_specs=[
                pl.BlockSpec((bq, G * D), q_block),
                pl.BlockSpec((kb, D), kv_block),
                pl.BlockSpec((kb, D), kv_block),
            ],
            out_specs=pl.BlockSpec((bq, G * D), q_block),
            scratch_shapes=[
                pltpu.VMEM((G, D, bq), jnp.float32),
                pltpu.VMEM((G, 1, bq), jnp.float32),
                pltpu.VMEM((G, 1, bq), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H * D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="tile_attention",
        interpret=interpret,
    )


# ------------------------------------------------------------------ latent
# A layer of LATENTS (models/latent_attention.py): a position keeps
# `[c ‖ k_r]`, R + Dr values that all heads share, and the scratch keeps the
# positions last, `[R + Dr, M]`. The second kernel below shares the walk
# above and nothing else. Its grid is (group of G heads, block of query
# rows, key step) with the WHOLE tile one block of query rows where it has
# up to `_LATENT_ROWS`, so that a key block `[R + Dr, kb]` is read and
# up-projected once a head group: K^T = W_uk^T c and V^T = W_uv^T c for the
# group's heads in one matmul each, `[G * Dn, kb]` and `[G * Dv, kb]`,
# rounded to the cache's type as the loop's einsums round them. The
# positions lie in the lanes, so K^T and V^T come out as the transposed
# scores `[kb, bq]` and the accumulator `[Dv, bq]` want them; q is handed
# over transposed by head, `[H, Dn + Dr, S]`, and the weights by output
# column, `[H * Dn, R]`. (My chip run, PR 51, a tile of 1,024 rows of 64
# heads at pos0 4,096: 2.58 ms against the loop's 11.26; scores held
# `[bq, kb]` 3.38; K and V expanded once by XLA before a walk over them
# 2.80; groups of 2 / 4 / 8 heads 2.68 / 2.58 / 2.86.)
_LATENT_ROWS = 1024
_LATENT_GROUP = 4
# the compiler counts 11.6 MB a step at 1,024 rows, 4 heads of 192 / 128 and
# key blocks of 512 in bf16 (q 2 x 2 MB, the latents 2 x 0.6, the weights
# 4 x 0.5, the output 2 x 1, the accumulators 2, a head's scores in float32
# 2): under the default 16 MiB, which a model served in float32 would pass
_LATENT_VMEM = 32 * 2 ** 20


def latent_group(H: int) -> int:
    """The heads of one grid step."""
    return block_of(H, _LATENT_GROUP)


def latent_fits(S: int, M: int, H: int, R: int, Dn: int, Dr: int,
                Dv: int) -> bool:
    """Whether the compiled latent kernel takes a tile of S rows against M
    positions of latents `R + Dr` wide: the tile, a key block, the latent
    and a head's unrotated key and its value whole lane tiles, the rotated
    key a half or a whole one."""
    return (S % _LANES == 0 and block_of(M) % _LANES == 0
            and R % _LANES == 0 and Dn % _LANES == 0 and Dv % _LANES == 0
            and Dr in (_LANES // 2, _LANES))


def _latent_kernel(pos_ref, first_ref, count_ref, q_ref, lat_ref, wuk_ref,
                   wuv_ref, o_ref, acc_ref, m_ref, l_ref, *, bq: int,
                   kb: int, group: int, scale: float):
    """One key block of latents into the running softmax of one block of
    query rows of `group` heads: `latent_attention.tile_attention`'s step
    (`_expand`, `_scores`, `_softmax_step`), the statistics and the
    float32 accumulator held here over the block's key steps."""
    j, i = pl.program_id(1), pl.program_id(2)
    R = wuk_ref.shape[1]
    Dn, Dv = wuk_ref.shape[0] // group, wuv_ref.shape[0] // group
    dtype = lat_ref.dtype

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = pos_ref[0] + j * bq
    k0 = (first_ref[j] + i) * kb
    whole = k0 + kb - 1 <= q0           # every pair of the block visible

    def fold(masked: bool):
        c, kr = lat_ref[:R, :], lat_ref[R:, :]
        kT = _dot(wuk_ref[...], c).astype(dtype)        # [G * Dn, kb]
        vT = _dot(wuv_ref[...], c).astype(dtype)        # [G * Dv, kb]
        if masked:
            ok = k0 + jax.lax.broadcasted_iota(jnp.int32, (kb, bq), 0) \
                <= q0 + jax.lax.broadcasted_iota(jnp.int32, (kb, bq), 1)
        for g in range(group):
            q = q_ref[g]                                # [Dn + Dr, bq]
            s = (_dot_tn(kT[g * Dn:(g + 1) * Dn], q[:Dn])
                 + _dot_tn(kr, q[Dn:])) * scale         # [kb, bq]
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            m = m_ref[g]
            m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(ok, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=0, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + _dot(
                vT[g * Dv:(g + 1) * Dv], p.astype(dtype))
            m_ref[g] = m_new

    live = i < count_ref[j]
    pl.when(live & whole)(lambda: fold(False))
    pl.when(live & jnp.logical_not(whole))(lambda: fold(True))

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        for g in range(group):
            out = acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)
            o_ref[:, g * Dv:(g + 1) * Dv] = out.T.astype(o_ref.dtype)


def latent_tile_attention(q, cache, pos0, w_uk, w_uv, scale: float, *,
                          max_rows: int = _LATENT_ROWS,
                          interpret: bool = False):
    """q [1, S, H, Dn + Dr] at positions pos0 + 0..S-1 against the layer
    of the scratch `cache` [1, R + Dr, M], which holds the tile's own
    latents and is read where it lies, with the up-projections w_uk
    [R, H, Dn] and w_uv [R, H, Dv] and the scores' `scale` (a Python
    number) -> [1, S, H, Dv] in q's type."""
    _, S, H, D = q.shape
    W, M = cache.shape[1:]
    R, _, Dn = w_uk.shape
    Dv = w_uv.shape[-1]
    if not latent_fits(S, M, H, R, Dn, D - Dn, Dv) or q.shape[0] != 1 \
            or jnp.ndim(pos0) or W != R + D - Dn:
        raise ValueError(
            f"the latent tile kernel takes one tile of whole {_LANES}-row "
            f"blocks at one start against whole key blocks of latents: got "
            f"q {q.shape}, a scratch {cache.shape}, w_uk {w_uk.shape}, "
            f"w_uv {w_uv.shape}")
    G = latent_group(H)
    bq, kb = blocks_of(S, M, G, max_rows)
    pos0 = jnp.asarray(pos0, jnp.int32)
    first, count = block_walk(pos0, S, M, 0, bq, kb)
    out = _latent_call(S, M, H, R, Dn, D - Dn, Dv, bq, kb, float(scale),
                       jnp.dtype(q.dtype), interpret)(
        jnp.reshape(pos0, (1,)), first, count, jnp.transpose(q[0], (1, 2, 0)),
        cache.reshape(W, M), w_uk.reshape(R, H * Dn).T,
        w_uv.reshape(R, H * Dv).T)
    return out.reshape(1, S, H, Dv)


@functools.lru_cache(maxsize=None)
def _latent_call(S: int, M: int, H: int, R: int, Dn: int, Dr: int, Dv: int,
                 bq: int, kb: int, scale: float, dtype, interpret: bool):
    """The latent kernel's `pallas_call` of one shape, built once, as
    `_call` builds the other's."""
    G = latent_group(H)

    def q_block(h, j, i, pos_ref, first_ref, count_ref):
        return h, 0, j

    def lat_block(h, j, i, pos_ref, first_ref, count_ref):
        # past the rows' last block: that block again (no new DMA)
        return 0, first_ref[j] + jnp.minimum(i, count_ref[j] - 1)

    def w_block(h, j, i, pos_ref, first_ref, count_ref):
        return h, 0

    def o_block(h, j, i, pos_ref, first_ref, count_ref):
        return j, h

    return pl.pallas_call(
        functools.partial(_latent_kernel, bq=bq, kb=kb, group=G,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H // G, S // bq, max_steps(M, 0, bq, kb)),
            in_specs=[
                pl.BlockSpec((G, Dn + Dr, bq), q_block),
                pl.BlockSpec((R + Dr, kb), lat_block),
                pl.BlockSpec((G * Dn, R), w_block),
                pl.BlockSpec((G * Dv, R), w_block),
            ],
            out_specs=pl.BlockSpec((bq, G * Dv), o_block),
            scratch_shapes=[
                pltpu.VMEM((G, Dv, bq), jnp.float32),
                pltpu.VMEM((G, 1, bq), jnp.float32),
                pltpu.VMEM((G, 1, bq), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H * Dv), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM),
        name="latent_tile_attention",
        interpret=interpret,
    )
