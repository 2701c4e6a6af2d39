"""Attention kernels.

`flash_attention` is a Pallas TPU kernel pair (tiled online-softmax forward
+ FlashAttention-2-style backward, VMEM-blocked for the MXU; see
/opt/skills/guides/pallas_guide.md conventions) wired up as a
`jax.custom_vjp`, so it is usable inside `jax.grad` train steps. All three
kernels walk a grid of (head, outer block, inner block), the inner axis a
reduction into VMEM scratch: `causal_block_range` says which inner blocks
a block meets under the causal mask and which of those the diagonal
crosses, so a step above the diagonal does nothing (and fetches nothing),
an interior step builds no mask, and only the diagonal tiles pay for one.
The row statistics (`lse`, `delta`) are `[B*H, 1, L]`, along the lanes.
Head dims that aren't lane-aligned (e.g. 64) are zero-padded to 128 outside
the custom_vjp — padding q/k with zeros leaves the logits unchanged and AD
slices the gradients back. `flash_attention` always runs the kernel: it
raises on lengths or blocks the kernel cannot tile (`flash_fits` says
which), and off the TPU the kernel only runs with `interpret=True`. The
choice between this kernel and `mha_reference` — by platform and shape —
belongs to `ops/dispatch.py` `attention(impl="auto")` alone.

The reference framework has no attention kernels at all (it orchestrates
torch models); these exist because long-context parallelism is first-class
here (SURVEY.md §5.7).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def mha_reference(q, k, v, causal: bool = True,
                  q_offset: int = 0, k_offset: int = 0,
                  scale: Optional[float] = None):
    """XLA attention: q[B,Lq,H,D], k/v[B,Lk,Hkv,D] -> [B,Lq,H,D].
    Supports GQA (H a multiple of Hkv) and absolute position offsets for
    block-parallel callers."""
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    scale = scale if scale is not None else D ** -0.5
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = jnp.arange(Lq) + q_offset
        kpos = jnp.arange(Lk) + k_offset
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


# ---------------------------------------------------------- the block walk
def causal_block_range(walk: str, i, block_q: int, block_k: int, n: int,
                       causal: bool = True):
    """The blocks one block meets, as `(masked, interior)`, each a
    half-open `(start, stop)` range of block indices.

    `walk="k"`: `i` is a Q block (rows `i*block_q ...`) and the ranges are
    over the `n` K blocks; `walk="q"`: `i` is a K block and the ranges are
    over the `n` Q blocks. Under the causal mask (row >= column) a block
    is *interior* when every element is visible (its last column is not
    past the Q block's first row), *masked* when the diagonal crosses it,
    and not met at all above the diagonal. Pure integer arithmetic: `i` may
    be a traced `program_id` (the kernels) or a Python int (the tests and
    `flash_block_counts`)."""
    if not causal:
        return (0, 0), (0, n)
    lo_clip = min if isinstance(i, int) else jnp.minimum
    if walk == "k":
        # K block j is interior iff (j+1)*bk - 1 <= i*bq, met iff
        # j*bk <= (i+1)*bq - 1
        n_int = lo_clip((i * block_q + 1) // block_k, n)
        hi = lo_clip(((i + 1) * block_q + block_k - 1) // block_k, n)
        return (n_int, hi), (0, n_int)
    # Q block j is met iff (j+1)*bq - 1 >= i*bk, interior iff
    # j*bq >= (i+1)*bk - 1
    lo = lo_clip((i * block_k) // block_q, n)
    first_int = lo_clip(((i + 1) * block_k - 1 + block_q - 1) // block_q, n)
    return (lo, first_int), (first_int, n)


def flash_block_counts(walk: str, Lq: int, Lk: int, block_q: int,
                       block_k: int, causal: bool = True) -> dict:
    """Tiles a head's walk computes, masks, and needs (those that hold a
    visible element): a count from shapes, for the tests and PERF.md. A
    tile is what one matmul pair covers: a block, or `_SUB` x `_SUB` of it
    where `_sub_tiles` cuts the block up."""
    n_q, n_k = Lq // block_q, Lk // block_k
    n_outer, n_inner = (n_q, n_k) if walk == "k" else (n_k, n_q)
    by = "q" if walk == "k" else "k"
    visited = masked = 0
    for i in range(n_outer):
        (m0, m1), (i0, i1) = causal_block_range(
            walk, i, block_q, block_k, n_inner, causal)
        for diagonal, blocks in ((True, m1 - m0), (False, i1 - i0)):
            tiles = [t for _, row in _sub_tiles(block_q, block_k, diagonal,
                                                by) for t in row]
            visited += blocks * len(tiles)
            masked += blocks * sum(m for _, m in tiles)
    tq, tk = _tile_shape(block_q, block_k)
    needed = sum(
        1 for qi in range(Lq // tq) for ki in range(Lk // tk)
        if not causal or (qi + 1) * tq - 1 >= ki * tk)
    return {"visited": visited, "masked": masked, "needed": needed}


def _in(j, rng):
    return (j >= rng[0]) & (j < rng[1])


def _visible(q_start, k_start, shape, q_axis: int):
    """row >= column for a score tile whose Q positions run along `q_axis`
    from `q_start` and K positions along the other from `k_start`."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qpos >= kpos


# A block (one grid step's operands) is computed in square tiles of this
# edge where the blocks are square themselves: the diagonal then runs from
# a block's corner, so which tiles are masked, whole, or above the diagonal
# is known when tracing, and a diagonal block of n x n tiles costs
# n (n + 1) / 2 of them, n with a mask. Smaller tiles than the blocks keep
# the wasted corner small while the grid stays coarse; every tile is
# unrolled into the kernel, which is what holds them at 512 (`choose_blocks`
# has the measurements).
_SUB = 512


def _tile_shape(block_q: int, block_k: int):
    """(q rows, k rows) of the tiles a block is computed in."""
    if block_q == block_k and block_q % _SUB == 0:
        return _SUB, _SUB
    return block_q, block_k


def _sub_tiles(block_q: int, block_k: int, diagonal: bool, by: str):
    """Static plan of one block, grouped by the axis a kernel accumulates
    along (`by="q"`: per Q rows, the K rows they meet; `by="k"`: the
    reverse): [(outer rows, [(inner rows, masked), ...]), ...], the tiles
    wholly above the diagonal left out."""
    tq, tk = _tile_shape(block_q, block_k)
    t_out, t_in = (tq, tk) if by == "q" else (tk, tq)
    n = block_q // tq               # tiles are square, or the block itself

    def below(a, c):                # outer tile a, inner tile c
        return c <= a if by == "q" else c >= a

    return [(slice(a * t_out, (a + 1) * t_out),
             [(slice(c * t_in, (c + 1) * t_in), diagonal and a == c)
              for c in range(n) if not diagonal or below(a, c)])
            for a in range(n)]


def _walk_block(group, j, ranges, causal: bool, block_q: int, block_k: int,
                by: str):
    """One grid step of a walk: run `group(outer rows, [(inner rows,
    masked), ...])` over the block's tiles if block `j` is interior, the
    same with the mask on the diagonal tiles if the diagonal crosses it,
    nothing if it lies above."""
    masked, interior = ranges

    def run(diagonal):
        for outer, inner in _sub_tiles(block_q, block_k, diagonal, by):
            group(outer, inner)

    if not causal:
        return run(False)
    pl.when(_in(j, interior))(lambda: run(False))
    pl.when(_in(j, masked))(lambda: run(True))


def _dot_nt(a, b):                  # a @ b.T without materialising b.T
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_tn(a, b):                  # a.T @ b
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, block_q: int, block_k: int, n_inner: int, causal: bool,
                scale: float):
    """Scores, and the output accumulator with them, are held transposed
    ([k rows, q rows], [D, q rows]): a row's running max and sum are then
    one lane each of a [1, q rows] vector, reduced and broadcast along the
    sublanes, where [q rows, 1] would fill one lane of every register and
    reduce across lanes. The accumulator is transposed back once a Q
    block."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def group(rows, tiles):
        q = q_ref[rows, :]
        acc, m, l = acc_ref[:, rows], m_ref[:, rows], l_ref[:, rows]
        for cols, masked in tiles:
            v = v_ref[cols, :]
            s = _dot_nt(k_ref[cols, :], q) * scale
            if masked:
                s = jnp.where(_visible(qi * block_q + rows.start,
                                       ki * block_k + cols.start, s.shape,
                                       1), s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=0, keepdims=True)
            acc = acc * alpha + _dot_tn(v, p.astype(v.dtype))
            m = m_new
        acc_ref[:, rows], m_ref[:, rows], l_ref[:, rows] = acc, m, l

    _walk_block(group, ki, causal_block_range("k", qi, block_q, block_k,
                                              n_inner, causal),
                causal, block_q, block_k, "q")

    @pl.when(ki == n_inner - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


# -------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, block_q: int, block_k: int, n_inner: int,
                   causal: bool, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def group(rows, tiles):
        q, do = q_ref[rows, :], do_ref[rows, :]
        # the statistics lie along the lanes: [1, rows] -> [rows, 1]
        lse = jnp.expand_dims(lse_ref[0, rows], -1)
        delta = jnp.expand_dims(delta_ref[0, rows], -1)
        acc = acc_ref[rows, :]
        for cols, masked in tiles:
            k = k_ref[cols, :]
            s = _dot_nt(q, k) * scale
            if masked:
                s = jnp.where(_visible(qi * block_q + rows.start,
                                       ki * block_k + cols.start, s.shape,
                                       0), s, NEG_INF)
            p = jnp.exp(s - lse)
            ds = p * (_dot_nt(do, v_ref[cols, :]) - delta)
            acc = acc + _dot(ds.astype(k.dtype), k)
        acc_ref[rows, :] = acc

    _walk_block(group, ki, causal_block_range("k", qi, block_q, block_k,
                                              n_inner, causal),
                causal, block_q, block_k, "q")

    @pl.when(ki == n_inner - 1)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                    block_k: int, n_inner: int, causal: bool, scale: float):
    """Scores are held transposed, [k rows, q rows]: `p^T @ dO` and
    `ds^T @ Q` are then plain matmuls, and the row statistics lie along
    the lanes as they do in HBM (a sublane broadcast, not a lane one)."""
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def group(cols, tiles):
        k, v = k_ref[cols, :], v_ref[cols, :]
        dk, dv = dk_acc[cols, :], dv_acc[cols, :]
        for rows, masked in tiles:
            q, do = q_ref[rows, :], do_ref[rows, :]
            s = _dot_nt(k, q) * scale
            if masked:
                s = jnp.where(_visible(qi * block_q + rows.start,
                                       ki * block_k + cols.start, s.shape,
                                       1), s, NEG_INF)
            p = jnp.exp(s - lse_ref[:, rows])
            dv = dv + _dot(p.astype(do.dtype), do)
            ds = p * (_dot_nt(v, do) - delta_ref[:, rows])
            dk = dk + _dot(ds.astype(q.dtype), q)
        dk_acc[cols, :], dv_acc[cols, :] = dk, dv

    _walk_block(group, qi, causal_block_range("q", ki, block_q, block_k,
                                              n_inner, causal),
                causal, block_q, block_k, "k")

    @pl.when(qi == n_inner - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# ------------------------------------------------------- blocks from shape
def _fit_block(target: int, length: int) -> int:
    b = target
    while b > 128 and length % b:
        b //= 2
    return min(b, length)


def choose_blocks(Lq: int, Lk: int, D: int):
    """(block_q, block_k) of a grid step, for all three kernels: a static
    function of the lengths and the (padded) head width. The largest
    power-of-two multiple of 128 up to the target that divides the length,
    so a short sequence is one (diagonal) block.

    Measured on one v5e (PERF.md section 6, PR 31; ms a call at [B*H, L, D]
    = [64, 4096, 128], forward / dq / dkv, block : tile): 512 : 512 -> 3.79
    / 4.03 / 4.51, 1024 : 1024 -> 3.24 / 3.51 / 4.18, 1024 : 512 -> 2.99 /
    3.22 / 3.83, 1024 : 256 -> 2.70 / 3.21 / 3.78, 2048 : 512 -> 2.63 /
    2.87 / 3.67, 2048 : 256 -> 2.09 / 2.83 / 3.53. A grid step costs about
    0.4 us and a block's first and last step more, so larger blocks in
    smaller tiles are faster for every kernel, at L 1024 (one block) too.
    But every tile of a block is unrolled into the kernel, and the step's
    lowering, which no compile cache saves, takes 0.02 s more a tile on the
    chip's host: 2048 : 256 (100 tiles a kernel) adds 8 s to every start of
    a training process, 2048 : 512 and 1024 : 256 (26) 2.3 s, 1024 : 512
    (7) 0.9 s. Hence 1024 in tiles of 512.
    VMEM a grid step at 1024 x 1024, D 128 (boom_attention_tricks.md
    section 6, adapted), bytes:
      blocked operands  2 buffers x 1024 x D x 2 each: q, k, v, o (2 MB);
                        dq: q, k, v, do, dq (2.5 MB); dkv: the same and
                        dk, dv (3 MB); the statistics 2 x 8 x 1024 x 4 each
      accumulators      1024 x D x 4 each: acc (0.5 MB); dk and dv (1 MB);
                        m and l as [1, 1024] rows, 32 KB each
      a tile's values   512 x 512 x 4 each for s, p (dp, ds), and their
                        bf16 copies: 3 to 5 MB
    6 to 9 MB of the 16 MB a v5e kernel may use by default (2048 : 1024
    does not fit). The operands grow with D, so a wider head takes the
    next block down."""
    target = 1024 if D <= 128 else 512
    return _fit_block(target, Lq), _fit_block(target, Lk)


def _call(kernel, walk, blocks, causal, scale, interpret, in_kinds,
          out_kinds, scratch, *operands):
    """One `pallas_call` over the grid (heads, outer blocks, inner blocks)
    of a walk: `walk="k"` has Q blocks outside and K blocks inside, "q"
    the reverse. An operand or result is "q" / "k" (a [block, D] slab of
    that axis; `operands` start with q and k) or "stat" (a [1, block_q] row
    of float32 statistics). Steps above the diagonal are skipped by the
    kernel; their index maps repeat the last block met, so no DMA is paid
    for them either."""
    (BH, Lq, D), Lk = operands[0].shape, operands[1].shape[1]
    block_q, block_k = blocks
    n_q, n_k = Lq // block_q, Lk // block_k
    n_outer, n_inner = (n_q, n_k) if walk == "k" else (n_k, n_q)

    def inner_block(i, j):
        if not causal:
            return j
        masked, interior = causal_block_range(walk, i, block_q, block_k,
                                              n_inner)
        if walk == "k":                         # met: [0, masked stop)
            return jnp.minimum(j, masked[1] - 1)
        # met: [masked start, n), which is empty for K rows past the last
        # Q row (Lk > Lq)
        return jnp.minimum(jnp.maximum(j, masked[0]), n_inner - 1)

    def q_of(i, j):
        return i if walk == "k" else inner_block(i, j)

    def k_of(i, j):
        return inner_block(i, j) if walk == "k" else i

    specs = {
        "q": pl.BlockSpec((None, block_q, D),
                          lambda b, i, j: (b, q_of(i, j), 0)),
        "k": pl.BlockSpec((None, block_k, D),
                          lambda b, i, j: (b, k_of(i, j), 0)),
        "stat": pl.BlockSpec((None, 1, block_q),
                             lambda b, i, j: (b, 0, q_of(i, j))),
    }
    shapes = {
        "q": jax.ShapeDtypeStruct((BH, Lq, D), operands[0].dtype),
        "k": jax.ShapeDtypeStruct((BH, Lk, D), operands[1].dtype),
        "stat": jax.ShapeDtypeStruct((BH, 1, Lq), jnp.float32),
    }
    return pl.pallas_call(
        functools.partial(kernel, block_q=block_q, block_k=block_k,
                          n_inner=n_inner, causal=causal, scale=scale),
        grid=(BH, n_outer, n_inner),
        in_specs=[specs[kind] for kind in in_kinds],
        out_specs=[specs[kind] for kind in out_kinds],
        out_shape=[shapes[kind] for kind in out_kinds],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(*operands)


# ------------------------------------------------- custom_vjp core (BH,L,D)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_core(causal, blocks, scale, interpret, qf, kf, vf):
    """`blocks` is a pinned (block_q, block_k), or None: chosen from the
    shape (`choose_blocks`)."""
    o, _ = _flash_fwd(causal, blocks, scale, interpret, qf, kf, vf)
    return o


def _flash_fwd(causal, blocks, scale, interpret, qf, kf, vf):
    D = qf.shape[-1]
    bq, bk = blocks = blocks or choose_blocks(qf.shape[1], kf.shape[1], D)
    o, lse = _call(_fwd_kernel, "k", blocks, causal, scale, interpret,
                   ["q", "k", "k"], ["q", "stat"],
                   [(D, bq), (1, bq), (1, bq)], qf, kf, vf)
    return o, (qf, kf, vf, o, lse)


def _flash_bwd(causal, blocks, scale, interpret, res, do):
    qf, kf, vf, o, lse = res
    D = qf.shape[-1]
    bq, bk = blocks = blocks or choose_blocks(qf.shape[1], kf.shape[1], D)
    # delta_i = rowsum(dO_i * O_i) — cheap, XLA fuses it; [BH, 1, Lq] as lse
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    operands = (qf, kf, vf, do, lse, delta)
    kinds = ["q", "k", "k", "q", "stat", "stat"]
    [dq] = _call(_bwd_dq_kernel, "k", blocks, causal, scale, interpret,
                 kinds, ["q"], [(bq, D)], *operands)
    dk, dv = _call(_bwd_dkv_kernel, "q", blocks, causal, scale, interpret,
                   kinds, ["k", "k"], [(bk, D), (bk, D)], *operands)
    return dq, dk, dv


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------ public entry
def flash_fits(Lq: int, Lk: int, block_q: Optional[int] = None,
               block_k: Optional[int] = None) -> bool:
    """Whether the kernel can tile these lengths: whole 128-row tiles, and
    pinned blocks (clamped to the length) of whole 128-row tiles that
    divide it. Blocks left to `choose_blocks` always do."""
    block_q, block_k = min(block_q or 128, Lq), min(block_k or 128, Lk)
    return not (Lq % 128 or Lk % 128 or block_q % 128 or block_k % 128
                or Lq % block_q or Lk % block_k)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    scale: Optional[float] = None, interpret: bool = False):
    """Tiled attention, differentiable. q[B,Lq,H,D], k/v[B,Lk,Hkv,D]
    (GQA ok). Head dim is zero-padded up to a multiple of 128 lanes.
    `block_q` / `block_k` pin the block shape (give both); left out, it
    is chosen from the shape (`choose_blocks`). Always the Pallas kernel, never the
    reference: raises ValueError on lengths it cannot tile."""
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    scale = scale if scale is not None else D ** -0.5
    if (block_q is None) != (block_k is None):
        raise ValueError("pin both block_q and block_k, or neither")
    if not flash_fits(Lq, Lk, block_q, block_k):
        raise ValueError(
            f"flash attention cannot tile Lq={Lq}, Lk={Lk} with blocks "
            f"({block_q}, {block_k}): lengths must be multiples of 128 "
            f"and of their block")
    blocks = None if block_q is None else (min(block_q, Lq),
                                           min(block_k, Lk))
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    Dp = (D + 127) // 128 * 128
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # layout: [B*H, L, D] so each grid cell works on one head's q block
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, Dp)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, Dp)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, Dp)
    out = _flash_core(causal, blocks, scale, interpret, qf, kf, vf)
    out = out.reshape(B, H, Lq, Dp).transpose(0, 2, 1, 3)
    return out[..., :D] if Dp != D else out
