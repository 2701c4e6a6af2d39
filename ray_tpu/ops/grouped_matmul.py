"""The expert layer over the rows that were routed: a grouped SwiGLU.

`routed_swiglu` is a Pallas TPU kernel for a group's rows `x [N, D]`, the
stacked experts `w_gate, w_up [E, D, F]` and `w_down [E, F, D]`, and a
layout of the group's picks sorted by expert (`sort_picks`): for each row
the sum over its picks (row, k) of
`gate * silu(x @ w_gate[e]) * (x @ w_up[e]) @ w_down[e]`, the three
projections of an expert's rows in one pass over F. It is what
`models/moe.py` runs in the serving forward where an expert's capacity is
the group's whole length, instead of a dispatch that multiplies every row
by every expert.

The layout. `sort_picks` lays the picks out by expert, and **each
expert's rows start on a boundary of a SPAN** (`SPAN` = two tiles of
`row_tile` rows; its count rounded up to the span): a span then belongs to
ONE expert, whose weight blocks stream past it once. A kernel that lets
two experts share a block of rows visits that block twice and streams the
second expert's weight blocks again, and where the step is bound by the
weights' stream that is a loss; so is a span of ONE tile where an expert's
load passes it: the expert is two spans and its weights stream twice (the
comment at `SPAN`). The static row count is the bound
`K * L + E * (span - 1)` rounded up to the span (`rows_bound`); which
spans are live, whose each is and how many rows it holds reach the kernel
as scalar-prefetched tables (`span_expert [S]`, `span_rows [S]`,
`n_live [1]`), as `ops/decode_attention.py` and `ops/tile_attention.py`
hand over slots and blocks. An expert that took no row is a group of zero
spans. What is COMPUTED follows the tile: a tile of a span that holds no
row is skipped, so the rows computed are each expert's picks rounded up
to the tile (`rows_computed`).

The walk. The grid is (span, block of `bf` columns of F). The group's rows
`[N, D]` and their float32 sum `[N, D]` stay in VMEM through the call.
Before a span's first step its rows are gathered out of them, a product
with the span's one-hot `[span, N]` (exact: one 1 a row); a step reads
the expert's blocks `w_gate[e][:, f]`, `w_up[e][:, f]` `[D, bf]` and
`w_down[e][f, :]` `[bf, D]` WHERE THEY LIE in the stacked arrays (the
block's index map reads the span's expert from the table: no op copies,
slices out or relays an expert's weights), and each tile that holds a row
rounds `h` and `u` to the rows' type, takes `silu(h) * u` in that type as
the dense form does, and adds its product with the down block to its rows
of a float32 accumulator `[span, D]`; after the span's last step the
accumulator, rounded to the rows' type as the dense form rounds an
expert's result, is summed back into the group's rows, a product with the
one-hot that holds each pick's gate (the dense form's combine: gates in
the rows' type, products summed in float32). Neither the hidden `[R, F]`
nor the sorted rows `[R, D]` nor their results pass through HBM. A dead
span (past `n_live`) repeats the last live step's blocks (no new DMA) and
computes nothing.

`routed_swiglu_reference` is the same in XLA (the rows gathered into the
layout, a loop over the live spans, their tiles that hold a row and the
blocks of F, a tile's arithmetic step for step with the same roundings,
each row's K results gathered back and weighed) and what runs off the
TPU, or where `fits` says the widths do not tile. Off the TPU the kernel
only runs with `interpret=True`; the choice belongs to the caller
(`models/moe.py` `_kernel_takes`).

What binds it (my chip runs, PR 55; `CHANGES.md` has the table): one layer
of Mixtral's step, 544 picks over 8 experts of 4,096 x 14,336 in bf16, is
2.82 GB of weights. The walk takes 3.86 ms a layer at blocks of 512
columns, which is what it takes with the products left out (3.87) and what
XLA's own einsums take to stream the same weights past 16 rows (3.78):
the stream, at 730-747 GB/s of the chip's 819. Blocks of 256 and 1,024
columns read 3.82 and 3.77; three or four buffers by hand in place of the
pipeline's two change nothing (3.83-3.85); tiles of 64 rows visit an
average expert twice (6.8 ms). In the cell's program the kernel takes
3.80 ms a layer, 742 GB/s as executed, where the dense dispatch's three
matmuls took 4.31.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _dot, _dot_tn

_LANES = 128
# the most rows of one pass of the MXU over a weight block: an expert's
# mean load in Mixtral's step is 58-68 rows (466-544 picks over 8)
_MAX_TILE = 128
_MIN_TILE = 8
# tiles of one SPAN, the rows an expert's weight blocks pass at once and
# the boundary its rows start on: an expert with up to twice its tile is
# one span and its weights stream once (at one tile a span, Mixtral's step
# met 8.5 live tiles a layer for 8 experts and streamed the ninth's 352 MB
# again: 4.12 ms a layer in the cell's program where eight take 3.86; my
# chip runs, PR 55)
SPAN = 2
# columns of F a step takes: three weight blocks of D x bf, twice (the
# pipeline's two buffers), 50 MB of the chip's 128 MiB of VMEM at
# Mixtral's widths; blocks of 512 under 64 MiB read 1.5% slower in the
# cell (`out_tok_s` 300.8 / 298.4 for 304.0 / 303.9; my chip runs, PR 55)
_BLOCK_F = 1024
_VMEM = 100 * 2 ** 20


def row_tile(L: int, K: int, E: int) -> int:
    """The rows of a tile for a group of L rows with K picks each over E
    experts: the experts' mean load rounded up to a power of two, within
    [`_MIN_TILE`, `_MAX_TILE`]."""
    mean = -(-K * L // E)
    return min(_MAX_TILE, max(_MIN_TILE, 1 << (mean - 1).bit_length()))


def rows_bound(n_picks: int, E: int, span: int) -> int:
    """The static row count of the sorted layout: every pick and, an
    expert, up to `span - 1` rows of padding to the next boundary, rounded
    up to whole spans."""
    return -(-(n_picks + E * (span - 1)) // span) * span


def block_f(D: int, F: int, itemsize: int) -> int:
    """The columns of F a step takes: the largest whole lane tiles up to
    `_BLOCK_F` that divide F and keep the step's six weight buffers in
    half of `_VMEM`."""
    bf = _BLOCK_F
    while bf > _LANES and (F % bf or 6 * D * bf * itemsize > _VMEM // 2):
        bf //= 2
    return bf


def fits(N: int, D: int, F: int, tile: int, dtype) -> bool:
    """Whether the compiled kernel takes N rows D wide in spans of `SPAN`
    tiles of `tile` against experts F wide: D and F whole lane tiles, the
    rows and a tile whole sublane tiles of the type, and what a step holds
    (six weight buffers, the group's rows and its result twice, a span's
    rows, and the two float32 accumulators) under `_VMEM`."""
    itemsize = jnp.dtype(dtype).itemsize
    bf, span = block_f(D, F, itemsize), SPAN * tile
    held = (6 * D * bf + (4 * N + span) * D) * itemsize + (span + N) * D * 4
    return (D % _LANES == 0 and F % bf == 0 and bf % _LANES == 0
            and tile % (32 // itemsize) == 0 and N % (32 // itemsize) == 0
            and held <= _VMEM * 7 // 8)


def sort_picks(expert, valid, E: int, span: int, late=None):
    """The sorted layout of one group's picks. `expert` [N, K] int32: the
    expert (of the E held, 0..E-1) each of N rows picked; `valid` [N, K]
    bool: the picks that are computed (a row a request owns, an expert that
    is held); `late` [N] bool: the rows that take their places after the
    others' (the decode rows behind a tile). A stable sort on (expert,
    late, row, k), each expert's rows from a boundary of `span` rows.

    -> (span_expert [S] int32: the expert of each live span, and past them
    the last live one's; span_rows [S] int32: the rows each holds, none
    past the live ones; n_live [1] int32; src [R] int32: the pick
    (row * K + k) that lies at each row of the layout, -1 where none;
    slot [N, K] int32: where each pick lies, any row where not valid)."""
    N, K = expert.shape
    P = N * K
    R = rows_bound(P, E, span)
    S = R // span
    late = 0 if late is None else late[:, None]
    key = jnp.where(valid, expert * 2 + late, 2 * E).reshape(P)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)    # [P] picks
    rank = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32))
    counts = jnp.sum(jax.nn.one_hot(key // 2, E, dtype=jnp.int32), axis=0)
    spans = -(-counts // span)                                 # [E]
    span_end = jnp.cumsum(spans)
    first_span = span_end - spans
    first_pick = jnp.cumsum(counts) - counts
    n_live = span_end[-1]
    # the expert of span s: the first whose spans end past s
    at = jnp.arange(S, dtype=jnp.int32)
    s = jnp.minimum(at, jnp.maximum(n_live - 1, 0))
    span_expert = jnp.minimum(
        jnp.sum(s[:, None] >= span_end[None, :], axis=1), E - 1
    ).astype(jnp.int32)
    span_rows = jnp.where(at < n_live, jnp.clip(
        counts[span_expert] - (at - first_span[span_expert]) * span, 0,
        span), 0).astype(jnp.int32)
    # the layout's rows: the i-th row of expert e is its i-th pick
    e_row = jnp.repeat(span_expert, span)                      # [R]
    i_row = jnp.arange(R, dtype=jnp.int32) - first_span[e_row] * span
    held = (jnp.arange(R) < n_live * span) & (i_row < counts[e_row])
    src = jnp.where(
        held, order[jnp.clip(first_pick[e_row] + i_row, 0, P - 1)], -1)
    e_pick = jnp.clip(expert, 0, E - 1).reshape(P)
    slot = first_span[e_pick] * span + rank - first_pick[e_pick]
    slot = jnp.where(valid.reshape(P), slot, 0).reshape(N, K)
    return (span_expert, span_rows, n_live.reshape(1).astype(jnp.int32),
            src, slot)


def rows_computed(span_rows, tile: int):
    """The rows the expert matmuls compute: each live span's whole tiles."""
    return (-(-span_rows // tile)).sum() * tile


def _swiglu_block(x, wg, wu, wd):
    """One block of F of one tile: the dense form's roundings (h and u to
    the rows' type, `silu(h) * u` in it) and the down block's product in
    float32."""
    f32 = jnp.float32
    h = _dot(x, wg).astype(x.dtype).astype(f32)
    u = _dot(x, wu).astype(x.dtype).astype(f32)
    # the rows' type has no arithmetic of its own in the kernel: float32,
    # rounded where an op of that type would round
    act = (h * jax.nn.sigmoid(h)).astype(x.dtype).astype(f32)
    return _dot((act * u).astype(x.dtype), wd)


def _kernel(se_ref, sr_ref, nl_ref, src_ref, gate_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, rows_ref, acc_ref, sum_ref, *, tile: int):
    """One block of F of one span. Before a span's first block its rows
    are gathered, and after its last its results summed back into the
    group's rows, both as products with the span's one-hot `[span, N]` (a
    row of it holds 1, or the pick's gate, at the pick's row of x)."""
    s, f = pl.program_id(0), pl.program_id(1)
    live = s < nl_ref[0]
    span, N = rows_ref.shape[0], x_ref.shape[0]

    @pl.when((s == 0) & (f == 0))
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def one_hot(value):
        at = jax.lax.broadcasted_iota(jnp.int32, (span, N), 1)
        return jnp.where(src_ref[...] == at, value, 0.0).astype(x_ref.dtype)

    @pl.when(live & (f == 0))
    def _():
        rows_ref[...] = _dot(one_hot(1.0), x_ref[...]).astype(rows_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(span // tile):
        # a tile of the span that holds no row computes nothing
        @pl.when(live & (h * tile < sr_ref[s]))
        def _():
            rows = pl.ds(h * tile, tile)
            acc_ref[rows, :] += _swiglu_block(
                rows_ref[rows, :], wg_ref[...], wu_ref[...], wd_ref[...])

    @pl.when(live & (f == pl.num_programs(1) - 1))
    def _():
        sum_ref[...] += _dot_tn(one_hot(gate_ref[...]),
                                acc_ref[...].astype(x_ref.dtype))

    @pl.when((s == pl.num_programs(0) - 1) & (f == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = sum_ref[...].astype(o_ref.dtype)


def routed_swiglu(x, w_gate, w_up, w_down, span_expert, span_rows, n_live,
                  src, slot, gates, *, tile: int, bf: int | None = None,
                  interpret: bool = False):
    """x [N, D], w_gate, w_up [E, D, F], w_down [E, F, D], `sort_picks`'
    five results for spans of whole tiles of `tile`, and `gates` [N, K]
    float32 (a pick's weight, 0 where it is not computed) -> [N, D] in x's
    type: each row's picks' results times their gates, summed in
    float32."""
    N, D = x.shape
    E, _, F = w_gate.shape
    S, = span_expert.shape
    R, = src.shape
    K = gates.shape[1]
    if not fits(N, D, F, tile, x.dtype) or R % S or (R // S) % tile:
        raise ValueError(
            f"the grouped kernel takes rows and experts of whole lane "
            f"tiles in whole spans of whole tiles: got x {x.shape}, {R} "
            f"rows in {S} spans of tiles of {tile}, experts {w_gate.shape}")
    bf = bf or block_f(D, F, x.dtype.itemsize)
    at = jnp.maximum(src, 0)
    return _call(N, R, D, E, F, R // S, tile, bf, jnp.dtype(x.dtype),
                 interpret)(
        span_expert, span_rows, n_live,
        jnp.where(src >= 0, at // K, -1).reshape(R, 1),
        jnp.where(src >= 0, gates.reshape(-1)[at], 0.0).reshape(R, 1),
        x, w_gate, w_up, w_down)


@functools.lru_cache(maxsize=None)
def _call(N: int, R: int, D: int, E: int, F: int, span: int, tile: int,
          bf: int, dtype, interpret: bool):
    """The `pallas_call` of one shape, built ONCE, as
    `tile_attention._call` is: a stack's layers share one traced kernel."""
    nf = F // bf

    def at(s, f, se_ref, sr_ref, nl_ref):
        # past the live spans: the last live step again (no new DMA)
        last = jnp.maximum(nl_ref[0] - 1, 0)
        return jnp.minimum(s, last), jnp.where(s > last, nf - 1, f)

    def row_block(s, f, *tables):
        return at(s, f, *tables)[0], 0

    def whole(s, f, *tables):
        return 0, 0

    def in_block(s, f, se_ref, *tables):
        s, f = at(s, f, se_ref, *tables)
        return se_ref[s], 0, f

    def down_block(s, f, se_ref, *tables):
        s, f = at(s, f, se_ref, *tables)
        return se_ref[s], f, 0

    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R // span, nf),
            in_specs=[
                pl.BlockSpec((span, 1), row_block),
                pl.BlockSpec((span, 1), row_block),
                pl.BlockSpec((N, D), whole),
                pl.BlockSpec((None, D, bf), in_block),
                pl.BlockSpec((None, D, bf), in_block),
                pl.BlockSpec((None, bf, D), down_block),
            ],
            out_specs=pl.BlockSpec((N, D), whole),
            scratch_shapes=[pltpu.VMEM((span, D), dtype),
                            pltpu.VMEM((span, D), jnp.float32),
                            pltpu.VMEM((N, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        name="grouped_swiglu",
        interpret=interpret,
    )


def routed_swiglu_reference(x, w_gate, w_up, w_down, span_expert, span_rows,
                            n_live, src, slot, gates, *, tile: int,
                            bf: int | None = None):
    """`routed_swiglu` in XLA, a tile's arithmetic step for step: the rows
    gathered into the layout, the live spans one after another, each tile
    that holds a row over the blocks of F into a float32 accumulator, and
    each row's K results gathered back and weighed."""
    N, D = x.shape
    F = w_gate.shape[-1]
    K = gates.shape[1]
    span = src.shape[0] // span_expert.shape[0]
    bf = bf or (block_f(D, F, x.dtype.itemsize) if F % _LANES == 0 else F)
    # a row of the layout that holds no pick computes row 0 again: finite,
    # and read by nobody
    rows = x[jnp.maximum(src, 0) // K]

    def one_tile(s, h, out):
        e, row0 = span_expert[s], s * span + h * tile
        xs = jax.lax.dynamic_slice_in_dim(rows, row0, tile)

        def one_block(f, acc):
            wg = jax.lax.dynamic_slice(w_gate, (e, 0, f * bf), (1, D, bf))[0]
            wu = jax.lax.dynamic_slice(w_up, (e, 0, f * bf), (1, D, bf))[0]
            wd = jax.lax.dynamic_slice(w_down, (e, f * bf, 0), (1, bf, D))[0]
            return acc + _swiglu_block(xs, wg, wu, wd)

        acc = jax.lax.fori_loop(0, F // bf, one_block,
                                jnp.zeros((tile, D), jnp.float32))
        return jax.lax.dynamic_update_slice_in_dim(
            out, acc.astype(x.dtype), row0, 0)

    def one_span(s, out):
        return jax.lax.fori_loop(
            0, -(-span_rows[s] // tile),
            lambda h, out: one_tile(s, h, out), out)

    y = jax.lax.fori_loop(0, n_live[0], one_span, jnp.zeros_like(rows))
    picked = jnp.where((gates != 0)[..., None],
                       y[slot.reshape(-1)].reshape(N, K, D), 0)
    return (picked.astype(jnp.float32) * gates[..., None]).sum(1).astype(
        x.dtype)
