"""Learned sparse attention: an indexer chooses, for every query, the
positions its attention heads attend (DeepSeek-V3.2-Exp's published
equations 1 and 2, beside grouped-query attention).

With `qI_t` [J, DI] the indexer's query heads at position t, `kI_s` [DI]
the ONE indexer key of position s and `w_t` [J] the head weights:

    I(t, s) = sum_j w_t[j] * relu(qI_t[j] . kI_s)        for s <= t
    S_t     = the min(topk, t + 1) positions s <= t of largest I(t, s),
              ties to the lower position
    o_t[h]  = sum_{s in S_t} softmax_s(q_t[h] . k_s[g(h)] / sqrt(D)) v_s[g(h)]

Three forms of the same mathematics, chosen by the caller from what it
holds (models/transformer.py `Attention`):

- `sparse_attention`: the plainest, a sequence against itself, the index
  scores and the mask whole. The uncached forward (training, the one-shot
  prefill of `make_generate_fn`).
- `sparse_prefill_attention`: a tile of S rows against a cache that holds
  them. Blocked over the keys, and only over the blocks that hold a live
  position: the index scores are accumulated over the indexer's heads (no
  [.., J, M] array), the topk-th score of each row is found exactly by a
  radix search over the scores' bits (no sort), and the masked attention
  runs block by block with a running softmax, so no [.., S, M] score of
  the attention heads exists.
- `sparse_decode_attention`: one row a slot against the slot's cache.
  The same radix threshold over the slot's live index scores and the
  row's own gives the set as a MASK (no sort), and K and V are attended
  where they lie in the pools, block by block over the live positions
  under that mask, with a running softmax (no gather): on a TPU by the
  kernel of `ops/decode_attention.py`, each slot's blocks up to its own
  last live one; elsewhere by an XLA loop over the same blocks.

Selection is exact in all three (no approximate top-k, no block-level
stand-in); at t + 1 <= topk it is every live position.

Below them, a second published mechanism: selection by BLOCK from
mean-pooled keys (`block_select` and the forms that attend its blocks).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import decode_attention, tile_attention

_NEG = -1e30
_MAX_BLOCK = 512        # keys a block of the blocked forms holds
_ONE_PASS = 1 << 20     # index scores of up to this many are one pass
_SELECT_BUCKETS = 4     # prefixes of the cache a tile's selection may run on


def _block_of(m: int) -> int:
    """The largest power of two up to _MAX_BLOCK that divides M."""
    return decode_attention.block_of(m, _MAX_BLOCK)


def _head_scores(qi, w, ki):
    """sum_j w[.., j] relu(qi[.., j, :] . ki[m]) -> [B, S, M] float32.
    ki [B, 1, DI, M]: the indexer has one KV head, positions last. A tile
    of queries takes one indexer head at a time, so the per-head scores
    never exist together ([S, J, M] float32 is 1.2 GB at 1024 x 18k); one
    row a slot takes the heads in one matmul (16 matmuls of one row each
    cost a decode step 0.18 ms a layer, my chip run, PR 34)."""
    ki = ki[:, 0]
    w = w.astype(jnp.float32)
    if qi.shape[1] == 1:
        s = jnp.einsum("bsjd,bdm->bsjm", qi, ki,
                       preferred_element_type=jnp.float32)
        return jnp.sum(w[..., None] * jax.nn.relu(s), axis=2)
    acc = None
    for j in range(qi.shape[2]):
        s = jnp.einsum("bsd,bdm->bsm", qi[:, :, j], ki,
                       preferred_element_type=jnp.float32)
        s = w[:, :, j, None] * jax.nn.relu(s)
        acc = s if acc is None else acc + s
    return acc


def index_scores(qi, w, ki, qpos, layer=None):
    """I [B, S, M] float32 of queries at absolute positions `qpos` [B, S]
    against the indexer keys `ki` [B, 1, DI, M] of positions 0..M-1 (or,
    with `layer`, that layer of a pool [n_layers, B, 1, DI, M]); -inf
    where the key lies after the query. A tile of queries is blocked over
    the keys, and a block past the last query's position is not read; a
    few rows (one a slot, in decode) score the slot in one pass: a block
    of [B, 512] is too little work to pay for a loop's step."""
    B, S = qpos.shape
    if layer is None:
        ki, layer = ki[None], 0
    DI, M = ki.shape[3], ki.shape[4]
    block = M if B * S * M <= _ONE_PASS else _block_of(M)
    n_live = jnp.minimum((jnp.max(qpos) + block) // block, M // block)

    def scores_of(i):
        kb = jax.lax.dynamic_slice(ki, (layer, 0, 0, 0, i * block),
                                   (1, B, 1, DI, block))[0]
        kpos = i * block + jnp.arange(block)
        return jnp.where(kpos[None, None, :] <= qpos[:, :, None],
                         _head_scores(qi, w, kb), -jnp.inf)

    with jax.named_scope("dsa_indexer"):
        if block == M:
            return scores_of(0)
        return jax.lax.fori_loop(
            0, n_live,
            lambda i, out: jax.lax.dynamic_update_slice_in_dim(
                out, scores_of(i), i * block, 2),
            jnp.full((B, S, M), -jnp.inf, jnp.float32))


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order. Only
    a NaN maps to 0, so 0 is free to mean 'no such position'; -inf maps
    above it."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def kth_largest(keys, k: int, bits: int = 2):
    """Per row of `keys` [.., M] uint32: the largest t with
    count(keys >= t) >= k, which is the k-th largest key (0 where the row
    has fewer than k keys above 0). A radix search from the top bits
    down, `bits` at a time: 32 / bits passes over the row, each counting
    against 2**bits - 1 candidates (2 bits: 1.4 ms for [1024, 18432]
    against 2.0 ms at 4 and a sort's 23, my chip run, PR 34; for a decode
    step's [8, 8705] 2 and 4 bits both 3 us against 95 us at 8 and a
    sort's 166, my chip run, PR 41: a pass is under a microsecond, so a
    few rows want no wider digit either). Exact."""
    prefix = jnp.zeros(keys.shape[:-1], jnp.uint32)
    digits = jnp.arange(1, 1 << bits, dtype=jnp.uint32)
    for p in range(32 // bits):
        shift = 32 - bits * (p + 1)
        cands = prefix[..., None] | (digits << shift)        # [.., n]
        cnt = jnp.sum(keys[..., None, :] >= cands[..., :, None],
                      axis=-1, dtype=jnp.int32)
        digit = jnp.sum(cnt >= k, axis=-1).astype(jnp.uint32)
        prefix = prefix | (digit << shift)
    return prefix


def select(scores, topk: int):
    """The selected set as a mask [.., M] over `scores` [.., M] (-inf
    where a position is not live): the topk largest, every live position
    where there are no more than topk, ties at the topk-th score to the
    lower position."""
    with jax.named_scope("dsa_select"):
        keys = jnp.where(scores > -jnp.inf, _sortable(scores),
                         jnp.uint32(0))
        thr = jnp.maximum(kth_largest(keys, topk), jnp.uint32(1))[..., None]
        above = keys > thr
        tied = keys == thr
        room = topk - jnp.sum(above, axis=-1, keepdims=True,
                              dtype=jnp.int32)
        # nearly always the tied are one position a row and all fit; the
        # ranking by position is computed only where some row's do not
        fits = jax.lax.cond(
            jnp.any(jnp.sum(tied, axis=-1, keepdims=True,
                            dtype=jnp.int32) > room),
            lambda: jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room,
            lambda: jnp.ones(tied.shape, bool))
        return above | (tied & fits)


def select_live(scores, topk: int, n_live):
    """`select` for a tile against a long cache of which `n_live`
    positions (traced) can be live: the search's passes run over the
    shortest of a few static prefixes of the cache that holds them, and
    not at all where every live position is selected (n_live <= topk).
    The result is `select`'s."""
    M = scores.shape[-1]
    step = -(-M // _SELECT_BUCKETS)
    prefixes = [m for m in range(step, M, step) if m > topk] + [M]
    if M <= topk:
        return scores > -jnp.inf

    def on_prefix(m):
        return lambda: jnp.pad(
            select(scores[..., :m], topk),
            [(0, 0)] * (scores.ndim - 1) + [(0, M - m)])

    branches = [lambda: scores > -jnp.inf] + [on_prefix(m)
                                              for m in prefixes]
    bounds = jnp.asarray([topk] + prefixes[:-1], jnp.int32)
    return jax.lax.switch(jnp.sum(n_live > bounds), branches)


def masked_attention(q, k, v, mask):
    """softmax over the masked positions, whole: q [B, S, H, D], k and v
    [B, M, Hkv, D], mask [B, S, M]. Grouped-query: each KV head serves
    H / Hkv query heads, against the unexpanded k and v."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    s = jnp.einsum("bshgd,bmhd->bhgsm", qg, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(mask[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgsm,bmhd->bshgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, D).astype(q.dtype)


def sparse_attention(q, k, v, qi, w, ki, topk: int):
    """A sequence against itself: positions 0..L-1 of every row."""
    B, L = q.shape[:2]
    qpos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
    mask = select(index_scores(qi, w, ki, qpos), topk)
    with jax.named_scope("dsa_attend"):
        return masked_attention(q, k, v, mask)


def _softmax_step(carry, s, mb, vb):
    """One block of keys into a running softmax: `carry` (the largest
    score so far, the sum, the weighted values), the block's scores s
    [B, Hkv, G, S, blk] with its mask `mb` (broadcast against them) and
    its values vb [B, blk, Hkv, D]."""
    m, l, acc = carry
    s = jnp.where(mb, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.where(mb, jnp.exp(s - m_new[..., None]), 0.0)
    scale = jnp.exp(m - m_new)
    l = l * scale + jnp.sum(p, axis=-1)
    acc = acc * scale[..., None] + jnp.einsum(
        "bhgsm,bmhd->bhgsd", p.astype(vb.dtype), vb,
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def _softmax_out(carry, q):
    """A running softmax's carry (`_softmax_step`), divided -> [B, S, H, D]
    in q's type."""
    B, S, H, D = q.shape
    _, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D).astype(q.dtype)


def _blocked_softmax(q, Hkv: int, n_live, block_of):
    """The running softmax over key blocks 0 .. n_live - 1 (traced) of a
    tile q [B, S, H, D] against Hkv KV heads -> [B, S, H, D] in q's type.
    `block_of(i, qg)`, with qg the queries grouped [B, S, Hkv, G, D],
    gives block i's scores [B, Hkv, G, S, blk], their mask and its values
    [B, blk, Hkv, D]."""
    B, S, H, D = q.shape
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    m0 = jnp.full((B, Hkv, G, S), _NEG, jnp.float32)
    return _softmax_out(jax.lax.fori_loop(
        0, n_live, lambda i, carry: _softmax_step(carry, *block_of(i, qg)),
        (m0, jnp.zeros_like(m0), jnp.zeros((B, Hkv, G, S, D), jnp.float32))),
        q)


def sparse_prefill_attention(q, k_cache, v_cache, qi, w, ki_cache, pos0,
                             topk: int):
    """A tile q [B, S, H, D] at absolute positions pos0 + 0..S-1 (pos0 a
    scalar or [B]) against caches [B, M, ..] that already hold the tile's
    own rows. The attention is blocked over the keys with a running
    softmax, over the blocks up to the tile's last position only."""
    B, S, H, D = q.shape
    M, Hkv = k_cache.shape[1], k_cache.shape[2]
    qpos = jnp.reshape(pos0, (-1, 1)) + jnp.arange(S)[None, :]
    qpos = jnp.broadcast_to(qpos, (B, S))
    sel = select_live(index_scores(qi, w, ki_cache, qpos), topk,
                      jnp.max(qpos) + 1)
    block = _block_of(M)
    n_live = jnp.minimum((jnp.max(qpos) + block) // block, M // block)

    def block_of(i, qg):
        kb = jax.lax.dynamic_slice_in_dim(k_cache, i * block, block, 1)
        vb = jax.lax.dynamic_slice_in_dim(v_cache, i * block, block, 1)
        mb = jax.lax.dynamic_slice_in_dim(sel, i * block, block, 2)
        mb = mb[:, None, None]                              # [B,1,1,S,blk]
        s = jnp.einsum("bshgd,bmhd->bhgsm", qg, kb,
                       preferred_element_type=jnp.float32) * D ** -0.5
        return s, mb, vb

    with jax.named_scope("dsa_attend"):
        return _blocked_softmax(q, Hkv, n_live, block_of)


def _kernel_reads(M: int, Hkv: int, D: int) -> bool:
    """Whether the decode row reads the pools through the Pallas kernel
    (ops/decode_attention.py): on a TPU, where the pools' shape fits it.
    Elsewhere the same blocks are read by an XLA loop."""
    return jax.default_backend() == "tpu" and decode_attention.fits(
        M, Hkv, D)


def _tile_kernel_takes(S: int, M: int, H: int, Hkv: int, D: int,
                       window: int = 0) -> bool:
    """Whether a prefill tile of S rows attends its scratch of M places
    through the Pallas kernel (ops/tile_attention.py): on a TPU, where the
    shapes fit it. Elsewhere `_tile_attention`'s XLA loop folds the same
    blocks."""
    return jax.default_backend() == "tpu" and tile_attention.fits(
        S, M, H, Hkv, D, window)


def _latent_tile_kernel_takes(S: int, M: int, H: int, R: int, Dn: int,
                              Dr: int, Dv: int) -> bool:
    """`_tile_kernel_takes`' twin for a layer of latents
    (models/latent_attention.py `tile_attention`): whether a tile of S
    rows of H heads, `Dn + Dr` wide for the scores and `Dv` for the
    values, attends its scratch of M positions of `R + Dr` values through
    the latent kernel of ops/tile_attention.py."""
    return jax.default_backend() == "tpu" and tile_attention.latent_fits(
        S, M, H, R, Dn, Dr, Dv)


def _latent_row_kernel_takes(M: int, H: int, W: int, R: int) -> bool:
    """`_kernel_reads`' twin for a pool of latents
    (models/latent_attention.py `row_attention`): whether one absorbed row
    a slot of H heads reads a pool of M positions of W values, the first R
    the value, through the latent kernel of ops/decode_attention.py."""
    return jax.default_backend() == "tpu" and decode_attention.latent_fits(
        M, H, W, R)


def _blocks_passed(lens, block: int, by_row: bool) -> int:
    """Whole key blocks of `block` positions and the row's own: up to each
    row's own last live block, or up to the longest row's, for every
    row."""
    if not by_row:
        lens = [max(lens, default=0)] * len(lens)
    return sum(-(-n // block) * block + 1 for n in lens)


def decode_positions_read(lens, M: int, Hkv: int, D: int) -> int:
    """Host arithmetic: the positions of K and V that
    `sparse_decode_attention` passes over for decode rows whose slots hold
    `lens` positions of caches [.., M, Hkv, D], each row's own counted
    too. Whole key blocks: up to each row's own last live one where the
    kernel reads, up to the longest row's, for every row, where the XLA
    loop does."""
    return _blocks_passed(lens, decode_attention.block_of(M),
                          _kernel_reads(M, Hkv, D))


def latent_positions_read(lens, M: int, H: int, W: int, R: int) -> int:
    """`decode_positions_read` of a pool of latents [.., W, M] under H
    heads (`latent_attention.row_attention`): each row's own blocks, of
    the latent kernel's length, where `_latent_row_kernel_takes`; the
    loop's blocks up to the longest row's, for every row, elsewhere."""
    if _latent_row_kernel_takes(M, H, W, R):
        return _blocks_passed(lens, decode_attention.latent_block_of(M), True)
    return _blocks_passed(lens, decode_attention.block_of(M), False)


def sparse_decode_attention(q, k_new, v_new, qi, w, ki_new, k_cache,
                            v_cache, ki_cache, lens, topk: int, layer=None):
    """One row a slot: q [B, 1, H, D] at position lens[b] (a scalar or
    [B]); the caches [B, M, ..] (or, with `layer`, that layer of the
    pools [n_layers, B, M, ..]) hold positions below lens[b] and are only
    read: the row's own k, v and indexer key come beside them and stand
    as position M of the selection. The selected set is a MASK over the
    slot's positions (the exact radix threshold of the tile's form, no
    sort), and K and V are attended where they lie in the pools, block by
    block over the live positions under that mask, with a running softmax
    into which the row's own key goes last: nothing is gathered, and a
    layer is never sliced out of the pool (it would be copied whole). On
    a TPU the blocks are read by `ops.decode_attention`'s kernel, each
    slot's up to ITS last live block; elsewhere (and where the pools'
    shape does not fit the kernel) by an XLA loop over the blocks up to
    the LONGEST live slot's."""
    B, _, H, D = q.shape
    if layer is None:
        k_cache, v_cache, ki_cache, layer = (k_cache[None], v_cache[None],
                                             ki_cache[None], 0)
    M, Hkv = k_cache.shape[2], k_cache.shape[3]
    lens = jnp.broadcast_to(jnp.reshape(lens, (-1,)), (B,))
    past = index_scores(qi, w, ki_cache, lens[:, None] - 1, layer)[:, 0]
    with jax.named_scope("dsa_indexer"):
        own = _head_scores(qi, w, ki_new)[:, 0]                    # [B,1]
    # exact, ties to the lower position: the row itself, the highest,
    # stands last and loses them. A score of -0.0 (every head's relu zero
    # under negative weights) ties with 0.0, as a sort has it; the
    # search's order of bits would put it below
    scores = jnp.concatenate([past, own], axis=-1)              # [B,M+1]
    # one search over the slot whole: on a prefix that holds the live
    # positions (`select_live`) it is 1-2 us a layer faster and four more
    # searches to trace, 3.8 s of a replica's warm-up (8.9 against 5.1 s,
    # the parent's 4.0; my chip runs, PR 41)
    sel = select(jnp.where(scores == 0, 0.0, scores), topk)
    attend = decode_attention.pool_decode_attention \
        if _kernel_reads(M, Hkv, D) else decode_attention.pool_decode_reference
    with jax.named_scope("dsa_attend"):
        m, l, acc = attend(q[:, 0], k_cache, v_cache, layer, lens,
                           sel[:, :M])
        # the row's own key and value, as the pools' rows are taken: one
        # row a KV head, met by all H query heads and counted by its own
        of_q = jnp.arange(H) // (H // Hkv)
        ok = sel[:, M:, None] & (jnp.arange(Hkv)[None, None, :]
                                 == of_q[None, :, None])       # [B,H,Hkv]
        s = jnp.einsum("bhd,bnd->bhn", q[:, 0], k_new[:, 0],
                       preferred_element_type=jnp.float32) * D ** -0.5
        carry = _softmax_step(
            (m[:, None, :, None], l[:, None, :, None],
             acc[:, None, :, None]),
            s[:, None, :, None], ok[:, None, :, None], v_new[:, 0, :, None])
        return _softmax_out(carry, q)


# ---------------------------------------------------------------------------
# Selection by BLOCK from mean-pooled keys (MiniCPM4 / InfLLM-V2), beside
# the per-token indexer above. With `c_j` the mean of the keys of one KV
# group over the kernel [stride j, stride j + kernel), a query at position t
# scores the kernels that end at or before t,
#
#     p[h, j] = softmax_j(q_h . c_j / sqrt(D)),  r_j = sum_{h in group} p[h, j]
#     R_b     = max r_j over the kernels that touch block b
#
# and attends, one selection a KV group, every position <= t of `topk`
# blocks: the first `init` blocks and the blocks that hold the last `window`
# positions always, the rest the best by R_b (ties to the lower block). With
# no more than `topk` blocks visible that is every position <= t. Two forms
# (models/transformer.py `Attention`): a tile against a cache that holds it,
# blocked over the keys with the running softmax of the per-token form; one
# row a slot against the slot's cache, in one pass, the row's own key and
# value beside it.

BlockGeometry = collections.namedtuple(
    "BlockGeometry", "block kernel stride init window topk")


def pool_keys(k_rows, n: int, geo: BlockGeometry):
    """`n` pooled keys [B, n, Hkv, D] float32 of `k_rows` [B, R, Hkv, D],
    whose first row is the first row of the first kernel:
    R >= stride (n - 1) + kernel. A kernel is kernel / stride segments of
    `stride` rows, summed once."""
    B, _, Hkv, D = k_rows.shape
    m = geo.kernel // geo.stride
    with jax.named_scope("blk_pool"):
        seg = k_rows[:, :geo.stride * (n + m - 1)].astype(jnp.float32) \
            .reshape(B, n + m - 1, geo.stride, Hkv, D).sum(axis=2)
        return sum(seg[:, i:i + n] for i in range(m)) / geo.kernel


def pooled_at(pos, geo: BlockGeometry, n_rows=None):
    """The row of a pooled-key cache that a call at position `pos` writes
    from. A tile (pos a scalar; `n_rows` None): the first kernel it may
    end. One decode row a slot (pos [B]) of a cache of `n_rows` rows: the
    kernel the row ends, or, where it ends none, the cache's last row,
    whose kernel would end past the cache and is seen by no query."""
    if n_rows is None:
        return jnp.maximum(
            pos // geo.stride - geo.kernel // geo.stride + 1, 0)
    ends = (pos + 1 >= geo.kernel) & ((pos + 1 - geo.kernel)
                                      % geo.stride == 0)
    return jnp.where(ends, (pos + 1 - geo.kernel) // geo.stride, n_rows - 1)


def tile_pooled_keys(k_cache, pos0, tile: int, geo: BlockGeometry):
    """The pooled keys a tile of `tile` rows at position `pos0` (traced)
    writes at row `pooled_at(pos0, geo)`, out of k_cache
    [B, M, Hkv, D] that holds the tile's rows -> [B, tile / stride, Hkv,
    D] float32. They are every kernel that ENDS inside the tile and,
    where the tile starts on no stride, one that ends past it: that one is
    written again, by the tile or decode row that ends it, before a query
    can see it."""
    n = max(tile // geo.stride, 1)
    first = pooled_at(pos0, geo)
    # the last kernel may read past the cache's end: zeros, never seen
    rows = jax.lax.dynamic_slice_in_dim(
        jnp.pad(k_cache, ((0, 0), (0, geo.kernel), (0, 0), (0, 0))),
        first * geo.stride, geo.stride * (n - 1) + geo.kernel, 1)
    return pool_keys(rows, n, geo)


def row_pooled_key(k_cache, k_new, lens, geo: BlockGeometry):
    """The pooled key one decode row a slot may end (written at row
    `pooled_at(lens, geo, rows)`): k_new [B, 1, Hkv, D] at position
    lens[b], beside k_cache [B, M, Hkv, D] that holds the positions below
    -> [B, 1, Hkv, D] float32."""
    M = k_cache.shape[1]
    start = jnp.clip(lens + 1 - geo.kernel, 0, M - geo.kernel)
    # a slice a slot: batched, XLA gathers them out of the whole pool's
    # layer (0.4 ms for 16 x 32 rows, my chip run, PR 39)
    win = jnp.stack([jax.lax.dynamic_slice_in_dim(
        k_cache[b], start[b], geo.kernel, 0)
        for b in range(k_cache.shape[0])])
    own = (jnp.arange(geo.kernel)[None, :] == (lens - start)[:, None])
    win = jnp.where(own[:, :, None, None], k_new, win)
    return pool_keys(win, 1, geo)


def block_select(q, kp, qpos, geo: BlockGeometry):
    """The selected blocks [B, Hkv, S, M / block] bool of queries q
    [B, S, H, D] at absolute positions `qpos` [B, S] against the pooled
    keys kp [B, NK, Hkv, D] (kernel j at row j)."""
    B, S, H, D = q.shape
    NK, Hkv = kp.shape[1], kp.shape[2]
    r, m = geo.block // geo.stride, geo.kernel // geo.stride
    NB = NK // r
    with jax.named_scope("blk_select"):
        qg = q.reshape(B, S, Hkv, H // Hkv, D)
        s = jnp.einsum("bshgd,bjhd->bhgsj", qg, kp.astype(q.dtype),
                       preferred_element_type=jnp.float32) * D ** -0.5
        ends = jnp.arange(NK) * geo.stride + geo.kernel
        seen = (ends[None, None, :] <= qpos[:, :, None] + 1)[:, None, None]
        s = jnp.where(seen, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(top > -jnp.inf, top, 0.0)),
                      0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        rj = jnp.sum(p, axis=2)                              # [B,Hkv,S,NK]
        # block b is touched by kernels r b - m + 1 .. r b + r - 1: a
        # max-pool of width r + m - 1, stride r, padding m - 1
        rj = jnp.pad(rj, ((0, 0),) * 3 + ((m - 1, 0),),
                     constant_values=-jnp.inf)
        R = functools.reduce(jnp.maximum, (
            rj[..., i:i + r * NB:r] for i in range(r + m - 1)))
        b0 = jnp.arange(NB) * geo.block
        t = qpos[:, None, :, None]                           # [B,1,S,1]
        forced = (b0 < geo.init * geo.block) | (
            b0 + geo.block > t - geo.window + 1)
        score = jnp.where(forced, jnp.inf, R)
        score = jnp.where(b0 <= t, score, -jnp.inf)
        return select(score, geo.topk)


def _positions_mask(sel, qpos, first: int, n: int, block: int):
    """[B, Hkv, 1, S, n]: the selected blocks `sel` [B, Hkv, S, n / block]
    spread over positions first .. first + n - 1, and causal."""
    kpos = first + jnp.arange(n)
    return (jnp.repeat(sel, block, axis=-1)
            & (kpos <= qpos[:, None, :, None]))[:, :, None]


def block_prefill_attention(q, k_cache, v_cache, kp_cache, pos0,
                            geo: BlockGeometry):
    """A tile q [B, S, H, D] at absolute positions pos0 + 0..S-1 (pos0 a
    scalar or [B]) against caches [B, M, Hkv, D] that already hold the
    tile's own rows, and pooled keys [B, M / stride, Hkv, D] that hold the
    kernels the tile ends. Blocked over the keys with a running softmax,
    over the blocks up to the tile's last position only."""
    B, S, H, D = q.shape
    M, Hkv = k_cache.shape[1], k_cache.shape[2]
    qpos = jnp.broadcast_to(
        jnp.reshape(pos0, (-1, 1)) + jnp.arange(S)[None, :], (B, S))
    sel = block_select(q, kp_cache, qpos, geo)
    kb = _block_of(M)
    n_live = jnp.minimum((jnp.max(qpos) + kb) // kb, M // kb)

    def block_of(i, qg):
        kblk = jax.lax.dynamic_slice_in_dim(k_cache, i * kb, kb, 1)
        vblk = jax.lax.dynamic_slice_in_dim(v_cache, i * kb, kb, 1)
        mb = _positions_mask(
            jax.lax.dynamic_slice_in_dim(sel, i * (kb // geo.block),
                                         kb // geo.block, 3),
            qpos, i * kb, kb, geo.block)
        s = jnp.einsum("bshgd,bmhd->bhgsm", qg, kblk,
                       preferred_element_type=jnp.float32) * D ** -0.5
        return s, mb, vblk

    with jax.named_scope("blk_attend"):
        return _blocked_softmax(q, Hkv, n_live, block_of)


def block_decode_attention(q, k_new, v_new, k_cache, v_cache, kp_cache, lens,
                           geo: BlockGeometry):
    """One row a slot: q [B, 1, H, D] at position lens[b]; the caches
    [B, M, Hkv, D] hold the positions below lens[b] and are only read: the
    row's own key and value come beside them (its block is always among
    the selected). kp_cache already holds the kernel the row ends, if it
    ends one. One pass over the slot: XLA's gather of the selected blocks
    is no faster at these lengths than the read (PERF.md section 6)."""
    B, _, H, D = q.shape
    M, Hkv = k_cache.shape[1], k_cache.shape[2]
    lens = jnp.broadcast_to(jnp.reshape(lens, (-1,)), (B,))
    sel = block_select(q, kp_cache, lens[:, None], geo)
    with jax.named_scope("blk_attend"):
        qg = q.reshape(B, 1, Hkv, H // Hkv, D)
        mask = _positions_mask(sel, lens[:, None] - 1, 0, M, geo.block)
        s = jnp.einsum("bshgd,bmhd->bhgsm", qg, k_cache,
                       preferred_element_type=jnp.float32) * D ** -0.5
        own = jnp.einsum("bshgd,bshd->bhgs", qg, k_new,
                         preferred_element_type=jnp.float32) * D ** -0.5
        s = jnp.concatenate([jnp.where(mask, s, _NEG), own[..., None]], -1)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgsm,bmhd->bshgd", p[..., :M].astype(
            v_cache.dtype), v_cache, preferred_element_type=jnp.float32) \
            + p[..., M].transpose(0, 3, 1, 2)[..., None] \
            * v_new.astype(jnp.float32)[:, :, :, None, :]
        return out.reshape(B, 1, H, D).astype(q.dtype)


def block_attention(q, k, v, geo: BlockGeometry):
    """A sequence against itself, positions 0..L-1 of every row: the tile
    form on the sequence as its own cache, padded to whole blocks."""
    L = q.shape[1]
    pad = -L % max(geo.block, geo.kernel)
    kc, vc = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (k, v))
    n = (L + pad) // geo.stride
    kp = pool_keys(jnp.pad(kc, ((0, 0), (0, geo.kernel), (0, 0), (0, 0))),
                   n, geo)
    return block_prefill_attention(q, kc, vc, kp.astype(k.dtype), 0, geo)
