"""Continuous-batching inference engine: fixed-shape slots, a carry on
the device, and a loop that runs one step program after another.

What it owns:

- ``n_slots`` decode slots, allocated once as the model's pools (K and V,
  ``[n_layers, n_slots, max_len, Hkv, D]``, and whatever else the model's
  layers keep: models/transformer.py ``cache_shapes`` names them,
  kv_cache.py ``SlotPool`` holds them) and donated through every step
  program. The cached forward's layers only read a pool; one write after
  them adds the step's new rows of all layers (``TransformerLM._decode``),
  so a program's output pool IS its donated input: the pools are mutated
  in place for the life of the engine. The prefix cache's blocks
  (``BlockStore``, fp or int8) hold K and V only: a model with any cache
  beyond those runs without a prefix cache.
- The slots' CARRY, one int32 array on the device that only the step
  programs write and that is all the host reads of a step: the slots'
  lengths [S], their last tokens [S], their temperatures' bits [S], the
  tokens the decode program last consumed [S] (a prompt's first token,
  where its tile wrote it), the key's two words, and where expert rows are
  counted their running sum [2] (``_build_fns``). The host keeps a mirror
  of the lengths and says what it alone knows in ONE packed array a
  program: the live mask (how an eviction or a cancel reaches the carry)
  and a tile's tokens, place, slot and temperature.

A step (``step``) runs read, decide, plan, dispatch, deliver, and between
two step programs the engine's thread does only what the next one needs:

- plan: reap cancels and deadlines, evict full slots, spend the prefill
  budget. A long prompt is split across steps (*chunked prefill*); what a
  step gives one request runs as ONE fixed-shape tile into a scratch
  cache, the tile chosen from the prompt's length, so every token of a
  prompt passes through one program whatever shared its steps. A span
  that does not end its prompt is a whole tile (``Scheduler.plan_prefill``
  gives a prompt its whole remainder, whole tiles, or nothing this step),
  so a prompt is cut into the same spans and costs the same
  ceil(remainder / tile) dispatches whatever else is in flight; a step
  holds a second span only where the first ended its prompt and the
  second ends its own (or the budget was raised to two tiles or more).
- dispatch: the step's programs back to back, no call waiting for a value.
  A step has one of TWO shapes. Where decode rows ride (every model but
  one with a sparse-attention indexer), a step that carries prompt is ONE
  program (``prefill``): the tile's rows and behind them one decode row a
  slot, everything but attention run once over all of them, so every
  weight streams once a step; a step with no prompt runs the decode
  program (``decode``: every occupied slot one token, each row attending
  and writing at its own length). Where they do not ride (the indexer's
  model, whose decode row is not bound by the weights' stream), the tile
  programs run without rows and the decode program right behind them. The
  tile's K/V never depend on the rows behind it.
- deliver: under the programs now running, what the step BEFORE decided
  goes to its consumers (tokens to ``RequestHandle`` queues, finishes,
  the recorder's spans).
- read: once, the carry as the last program left it.
- decide: each live request's token is appended, its finish decided (EOS,
  max tokens; slot capacity, cancellation and deadline at the next plan)
  and its slot freed, at once reusable. What was decided is held for the
  next step's deliver; where no work follows it is delivered at once.

Shapes are static everywhere: the carry, prompt tiles [1, T] (with the
slots' rows, [1, T + n_slots]) with T ``prefill_budget`` and, where that
is four chunks or more, a handful of shorter lengths (``prefill_tiles``),
every one compiled when the engine is built. XLA compiles the tile
programs, the slot insert and the decode step, and nothing recompiles
across admissions and evictions: ``decode_compile_count`` counts decode
retraces and tests assert it stays at 1.

What the rows and tiles read of the pools is counted by host arithmetic
on the lengths, which the model layer owns (``decode_rows_read``,
``tile_attention_layers``): this module knows no kind of layer by name.

Sampling is shared with ``make_generate_fn`` via models/sampling.py:
greedy engine output is bit-identical to the one-program generator.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu._private import compile_cache, events
from ray_tpu.inference import kv_cache
from ray_tpu.inference.scheduler import (FINISH_LENGTH, PrefillChunk,
                                         Request, RequestHandle,
                                         RequestState, Scheduler)


def prefill_tiles(chunk: int, budget: int) -> tuple:
    """The static lengths T of a prefill tile, ascending (the program of
    a step that carries one has T + n_slots rows: the slots' decode rows
    ride behind the tile): the budget in whole chunks, and below it a
    quarter of the tile above (in whole chunks) while that is a chunk or
    more. A handful however large the
    ratio ({32, 128, 512, 2048} at 16 / 2048), ONE member where the
    budget is under four chunks ({256} at 128 / 256; the budget <= chunk
    defaults): a tile costs a trace, a lowering and an executable's load
    at every start (2.6-6.1 s warm in a replica, most of it the trace:
    PERF.md section 5, "Where set-up goes"), and a tile a quarter
    the size saves at most three quarters of a compute-bound call and
    next to nothing of one bound by the weights' stream. A prompt runs
    in the smallest tile that holds the longest span a step can give it
    (``InferenceEngine._tile_of``)."""
    tiles = [max(1, -(-budget // chunk)) * chunk]
    while tiles[0] >= 4 * chunk:
        tiles.insert(0, -(-tiles[0] // (4 * chunk)) * chunk)
    return tuple(tiles)


PHASES = ("plan", "dispatch", "read", "emit")
# a launch phase (`events.launch_phase`) and its seconds' key in `stats()`
_STARTUP_KEYS = {"callable_init": "startup_init_s",
                 "weights": "startup_weights_s",
                 "engine": "startup_engine_s",
                 "engine.pools": "startup_pools_s",
                 "engine.programs": "startup_programs_s"}
# the scalars between the live mask and the tokens of the array a tile
# program takes from the host (`InferenceEngine._tile_args`)
_TILE_HEAD = 6


class _StepPhases:
    """The marks of an engine step, taken once and read three ways: the
    profiler's trace (`engine.step` with a span a phase under it, on the
    engine's thread, beside the device's programs on one clock), the
    step's one flight-recorder record (`plan_ms` ... `between_ms` on
    `engine.decode`) and `stats()` (a request's wait and its prefill
    span are counted from the `t_mono` of the step of its first span).

    A step is tiled by four phases, each entered as often as the step
    needs it (`enter` ends the phase before at the same clock read), in
    the order of `InferenceEngine.step`: plan (reap, evictions, the
    prefill plan), dispatch (a program's one host array and its call
    until the call returns, and the device-side copies a prompt's end or
    a prefix hit issues; all the step's programs back to back), emit
    (DELIVERY, under the programs now running: the tokens and finishes
    the step BEFORE decided go to their consumers, its record and the
    spans' ends to the recorder; then this step's counters), read (the
    thread blocks on the last program's output, once) and emit again
    (the decision, from the tokens' arrival on: the walk over the rows,
    finishes, freed slots, `on_step`; nothing is handed to a consumer
    here unless no work follows). A step's record therefore carries in
    `emit_ms` the delivery of the step before it and its own decisions.
    `engine.step` carries `step`, `t_mono` (time.monotonic()) and `t_wall`
    (time.time()): the pair by which a reader lays the harness's
    monotonic stamps and the recorder's wall-clock spans on the trace."""

    __slots__ = ("ms", "between_ms", "t_wall", "_t0", "_name", "_t", "_ann",
                 "_step_ann", "_t_end")

    def __init__(self):
        self._name = self._t_end = None     # no step open, none ended yet

    def begin(self, step: int, t_mono: float):
        self.t_wall = time.time()
        self._t0 = t = time.perf_counter()
        # this step's start less the last step's end: the loop between
        # two steps, its lock and its wait for work
        self.between_ms = 0.0 if self._t_end is None \
            else (t - self._t_end) * 1e3
        self.ms = dict.fromkeys(PHASES, 0.0)
        self._step_ann = events.annotate(
            "engine.step", step=step, t_mono=t_mono, t_wall=self.t_wall)
        self._step_ann.__enter__()
        self._open("plan", t)

    def _open(self, name: str, t: float):
        self._name, self._t = name, t
        self._ann = events.annotate("engine." + name)
        self._ann.__enter__()

    def enter(self, name: str):
        """End the phase in force and begin `name`: one clock read."""
        if name == self._name:
            return
        t = time.perf_counter()
        self.ms[self._name] += (t - self._t) * 1e3
        self._ann.__exit__(None, None, None)
        self._open(name, t)

    def end(self, span=None, after=None, **attrs):
        """The step's end: `span` (its `engine.decode`, where rows
        decoded) ends at this mark with the phases on it, which the
        recorder is told with the next delivery (appended to `after`:
        (function, keywords)); the annotations close. A second call does
        nothing: an exception's way out of a step."""
        if self._name is None:
            return
        self._t_end = time.perf_counter()
        self.ms[self._name] += (self._t_end - self._t) * 1e3
        if span is not None:
            after.append((span.end, dict(
                end=self.t_wall + (self._t_end - self._t0),
                between_ms=round(self.between_ms, 4), **attrs,
                **{k + "_ms": round(v, 4) for k, v in self.ms.items()})))
        self._ann.__exit__(None, None, None)
        self._step_ann.__exit__(None, None, None)
        self._name = None


@dataclasses.dataclass
class EngineConfig:
    """Knobs of the slot pool and admission policy.

    n_slots: decode batch width (slots advance together every step).
    max_len: per-slot KV capacity (prompt + generated tokens).
    prefill_chunk: the length of a prefix-cache block, and the least a
        prefill tile can be (a prefill call's static shape is [1, T],
        its tail padded; see ``prefill_tiles``).
    prefill_budget: max prompt tokens admitted per engine step, and the
        largest tile: what a step gives one request runs as one call,
        in the tile of the prompt's length (a prompt of the budget's
        length or more: this one), and the step's decode rows ride in
        that call. What a step has left when a prompt ends goes to the
        next prompt only if it ends that one too (or, a budget raised
        at run time, in whole tiles): a call carries a whole tile or a
        prompt's end, never the head of a prompt it cannot finish.
        The knob that trades TTFT (higher = prompts land faster, and a
        call's weight reads are shared by more tokens) against
        inter-token latency of in-flight decodes (lower = a step that
        carries prompt is shorter).
    eos_id: default EOS (<0 disables); per-request override on Request.
    temperature/top_k/top_p: default sampling (temperature has a
        per-request override; top_k/top_p are compiled in).
    """
    n_slots: int = 4
    max_len: int = 512
    prefill_chunk: int = 64
    prefill_budget: int = 64
    eos_id: int = -1
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    cache_dtype: Any = None       # default: model activation dtype
    # prefix-block format (kv_cache.py BlockStore): "int8" stores the
    # BLOCKS as int8 values + fp32 per-(position, head) scale rows —
    # ~itemsize*D/(D+4) more cached chunks per HBM byte, and the disagg
    # hand-off ships the same compressed spans. The decode slot pool
    # stays full precision (it is transient and donated through the hot
    # program). The miss path write-throughs each completed chunk and
    # reloads the dequantized values, so greedy output stays
    # bit-identical between a prefix-cache hit and the miss that
    # populated it.
    kv_quant: str = "none"
    # radix/prefix KV cache (prefix_cache.py): extra cache-only slots of
    # the SAME [n_layers, 1, max_len, Hkv, D] shape as decode slots,
    # carved into prefill_chunk-aligned blocks that hold completed
    # prefill spans. 0 disables. Admission with a trie hit copies the
    # matched blocks instead of re-running prefill over them; the copy
    # programs are fixed-shape, so the compile-once invariant holds.
    prefix_cache_slots: int = 0


class InferenceEngine:
    """Continuous-batching engine over one model + params (optionally on
    a parallel mesh: params stay wherever the caller sharded them; the
    KV pool shards batch (slots) over the data axes and KV heads over
    `tensor`, same as make_generate_fn's cache)."""

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 mesh=None, rules=None, seed: int = 0):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.params = params
        self.config = config or EngineConfig()
        self.mesh = mesh
        cfg = self.config
        mcfg = model.cfg
        if cfg.max_len > mcfg.max_seq_len:
            raise ValueError(
                f"max_len={cfg.max_len} exceeds the model's "
                f"max_seq_len={mcfg.max_seq_len}")
        from ray_tpu.models import moe, transformer
        beyond = sorted(set(transformer.cache_shapes(mcfg, 1, 1))
                        - {"k", "v"})
        if beyond and cfg.prefix_cache_slots > 0:
            raise ValueError(
                f"the model keeps caches beyond K and V ({', '.join(beyond)}"
                f": an indexer's keys, pooled keys, a recurrent state, a "
                f"convolution's tail, a sliding window's ring, a latent), "
                f"which prefix blocks do not carry: run it with "
                f"prefix_cache_slots=0")
        dtype = cfg.cache_dtype or mcfg.dtype
        self._kv_quant = kv_cache.check_format(cfg.kv_quant)
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._stop = False
        self._thread: Optional[threading.Thread] = None

        # the tiles a prefill call may take. A store that is written
        # through chunk by chunk (_publish_chunk: a chunk attends what
        # the store gives back for the chunks before it, which is what
        # makes a prefix hit bit-identical to the miss that filled it)
        # keeps the chunk as the only tile
        self._write_through = (cfg.prefix_cache_slots > 0
                               and kv_cache.writes_through(self._kv_quant))
        self._prefill_tiles = prefill_tiles(
            cfg.prefill_chunk, cfg.prefill_chunk if self._write_through
            else cfg.prefill_budget)
        # scratch is the largest tile longer than a slot so a padded
        # tile can never clamp its write window back onto real entries
        scratch_len = cfg.max_len + self._prefill_tiles[-1]
        with events.launch_phase("engine.pools"):
            self._slots = kv_cache.SlotPool(
                mcfg, cfg.n_slots, cfg.max_len, cfg.max_len, scratch_len,
                dtype, mesh, rules)
            # prefix blocks: prefix_cache_slots more rows of a slot's
            # shape, allocated after the slots' pools
            self.prefix_cache = self._blocks = None
            if cfg.prefix_cache_slots > 0:
                from ray_tpu.inference.prefix_cache import RadixPrefixCache
                self._blocks = kv_cache.BlockStore(
                    mcfg, cfg.prefix_cache_slots, cfg.max_len,
                    cfg.prefill_chunk, dtype, cfg.kv_quant, mesh)
                self.prefix_cache = RadixPrefixCache(cfg.prefill_chunk,
                                                     self._blocks.n_blocks)
        # the pools' bytes as `stats()` names them: all of them, and of
        # those beyond K and V the ones this model keeps
        self._pool_bytes = {"kv_pool_bytes": self._slots.nbytes(), **{
            key: self._slots.nbytes(names)
            for key, names in transformer.POOL_BYTES_KEYS.items()
            if names[0] in self._slots.shapes}}
        self.sched = Scheduler(cfg.n_slots, cfg.prefill_budget,
                               default_temperature=cfg.temperature,
                               eos_id=cfg.eos_id,
                               chunk_size=cfg.prefill_chunk,
                               prefix_cache=self.prefix_cache,
                               tile=self._prefill_tiles[-1])

        # the host's mirror of the carry's lengths, for its own
        # bookkeeping (`max_len` evictions, the `*_rows_*` counters): it
        # knows them without reading
        self._lengths = np.zeros((cfg.n_slots,), np.int32)

        self.decode_compile_count = 0
        self.prefill_compile_count = 0
        self.prefill_dispatches = 0
        self.prefill_tokens = 0       # real prompt tokens, not padding
        self.steps = 0
        # steps in which a prefill span and live decode rows ran as ONE
        # program (of `steps`; the rest are decode-only, prefill with no
        # slot live, or idle)
        self.fused_steps = 0
        # steps whose first program was issued while what the step before
        # decided was still undelivered (of `steps`): its tokens reached
        # their consumers under this step's program
        self.issued_ahead = 0
        self.tokens_generated = 0
        # what the decode rows read of the slots' pools, summed over the
        # rows of every step: host arithmetic on the lengths, which the
        # model layer does for each kind of attention it has
        # (`decode_rows_read`: the `*_rows_*` keys of `stats()`)
        self._rows_read_of = transformer.decode_rows_read(mcfg, cfg.max_len)
        self._rows_read = self._rows_read_of([])
        # a tile of T rows: the layers whose tile attends its scratch (or
        # its ring, which must hold the tile beside its window) by
        # `_tile_attention` and those of them the Pallas kernel takes
        # (ops/tile_attention.py: on a TPU, where the shapes fit), known
        # from the shapes each tile program is built on (the largest
        # first: the one a ring too short is told of) and summed over the
        # prefill dispatches
        self._tile_layers = {
            T: transformer.tile_attention_layers(mcfg, T, scratch_len, dtype)
            for T in self._prefill_tiles[::-1]}
        self.tile_attn_layers = 0
        self.tile_kernel_layers = 0
        # a model whose last layers keep no cache
        # (`transformer.cacheless_tail`): the rows the tile programs held
        # (a tile and the slots' rows behind it), summed over the prefill
        # dispatches, and those of them that entered those layers: a
        # number the model hands back with the caches when a program is
        # traced, taken where it picks the rows (`TransformerLM._decode`),
        # kept here by the program's rows
        self._has_tail = transformer.cacheless_tail(mcfg) < mcfg.n_layers
        self._tail_rows: dict = {}
        self.tile_rows = 0
        self.tail_rows_run = 0
        # an expert layer that holds a share of its experts, or whose
        # capacity is the whole group at every length (its tile then runs
        # the grouped form, whose rows follow the routing): [rows the
        # expert matmuls computed, picks of real rows that landed on a held
        # expert], summed over layers and calls (models/moe.py sows them).
        # Each program adds its pair to a running sum in the carry, which
        # comes back in the array the step reads anyway (it wraps at 32
        # bits; `_read` adds the difference since the last one read).
        # With every expert held under the dense dispatch both counts are
        # the shapes' (E x C, K x rows): nothing to read, and the carry
        # holds no sum
        # (and, where the routing is in groups and the layer holds a share,
        # two more: the real rows with a pick on a held expert, the real
        # rows; `moe.counts_hits`)
        self._count_moe = moe.rows_follow_routing(mcfg)
        self._moe_width = 4 if moe.counts_hits(mcfg) else 2
        self._moe_counts = np.zeros((self._moe_width,), np.int64)
        self._moe_seen = np.zeros((self._moe_width,), np.uint32)
        # disagg hand-off accounting (serve/disagg.py)
        self.kv_exports = 0
        self.kv_imports = 0
        self.remote_prefix_tokens = 0
        self.on_step: Optional[Callable[[Dict], None]] = None
        # flight-recorder root for engine-owned work that belongs to no
        # single request (multi-request decode batches)
        self._trace_id = events.new_trace_id()
        self._kv_itemsize = int(jnp.dtype(dtype).itemsize)
        self._phases = _StepPhases()
        # a request's wait, counted where it happens: requests whose
        # first span ran and the seconds they had waited for it since
        # submit (the `queue_wait_ms` of their `engine.slot` spans);
        # first tokens, and the seconds from a request's first span's
        # step to its first token. TTFT at the replica is their sum
        self.admitted = 0
        self.queue_wait_s = 0.0
        self.first_tokens = 0
        self.prefill_span_s = 0.0
        # the recorder's work of a step (a span's end: (function,
        # keywords)), made with the tokens' delivery (`_deliver`)
        self._after: List[tuple] = []
        with events.launch_phase("engine.programs"):
            self._build_fns()
            self._carry = self._new_carry(seed)
            self._compile_prefill_tiles()

    # ------------------------------------------------------------ device fns
    def _mesh_ctx(self):
        if self.mesh is None:
            import contextlib
            return contextlib.nullcontext()
        from ray_tpu.parallel.mesh import use_mesh
        return use_mesh(self.mesh)

    def _build_fns(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.sampling import sample_logits_dynamic
        cfg = self.config
        model = self.model
        top_k, top_p = cfg.top_k, cfg.top_p
        # donation rebinds the pool buffers in place — on every backend,
        # so the CPU tests exercise the same rebinding the chip runs
        # (a read of a donated buffer after its call raises there too)

        names = tuple(self._slots.shapes)
        n = len(names)
        S = cfg.n_slots
        count_moe, moe_width = self._count_moe, self._moe_width
        # the slots' decode rows ride in the program of a step's prefill
        # tile, and the weights stream once for both. The other shape of
        # a step, the tile program without them and two calls, is kept for
        # a model with an indexer: while its decode row sorted and
        # gathered (8.8 of 15.55 ms) riding saved nothing (one program
        # 57.2-59.4 ms against 41.7 + 15.55) and a first token waited for
        # the rows' work (my chip runs, PR 35; PERF.md section 6). The row
        # reads its cache in place since PR 41 and this was not measured
        # again (section 7)
        ride = self._ride = not model.cfg.index_heads

        def forward(params, tokens, pools, idx, real=None, slots=None,
                    **kw):
            """-> (logits, the pools (then the slots' pools), the call's
            expert-layer counts summed over the layers: int32[2], None
            where not counted). `real` [B, L] bool: the rows a request
            owns, where not all. `slots`: the second cache of a tile's
            call, whose decode rows end the sequence
            (TransformerLM._decode)."""
            cache = dict(zip(names, pools), idx=idx)
            if real is not None:
                cache["real"] = real
            if slots is not None:
                cache["slots"] = slots
            out = model.apply({"params": params}, tokens, cache=cache, **kw,
                              mutable=["counters"] if count_moe else False)
            (logits, new), counted = out if count_moe else (out, None)
            if "tail_rows" in new:      # at trace time, a program once
                self._tail_rows[tokens.shape[1]] = new["tail_rows"]
            new = tuple(c[name] for c in (new, new.get("slots"))
                        if c is not None for name in names)
            if not count_moe:
                return logits, new, None
            # one pair a layer (stacked where the layers are scanned)
            return logits, new, sum(c.reshape(-1, moe_width).sum(0)
                                    for c in jax.tree.leaves(counted))

        # Every step program ends its arguments with the slots' CARRY and
        # ONE packed int32 array from the host, and hands the carry back
        # first, advanced. The carry is ONE int32 array too (`_new_carry`),
        # which is also all the host reads of a step: the slots' lengths
        # [S], their last tokens [S], their temperatures' bits [S], the
        # tokens the decode program last consumed [S] (a prompt's first
        # token, where its tile wrote it), the key's two words, and where
        # expert counts are kept their running sum [2] (it wraps; the host
        # takes differences). One array because a small output costs a
        # call 75 us of the host's time on the chip unless it is donated,
        # and four small donated inputs cost the compiler's memory-space
        # assignment one of a layer's K / V copies (below).
        # Only the programs write it: a live row's length + 1 and its
        # sampled token, the key split (by decode), and by the tile that
        # ends a prompt the new slot's length, temperature and first
        # token. The host's array starts with the live mask [S]: how an
        # eviction or a cancel reaches the carry (a slot not live is not
        # advanced, and nothing reads its entries before a prompt's last
        # tile has written them).
        def unpack(state):
            """-> lengths, toks, temps, fed, rng, moe (a tuple: int32[2]
            where counted)"""
            rows = [state[i * S:(i + 1) * S] for i in range(4)]
            rows[2] = jax.lax.bitcast_convert_type(rows[2], jnp.float32)
            rng = jax.lax.bitcast_convert_type(state[4 * S:4 * S + 2],
                                               jnp.uint32)
            return (*rows, rng, (state[4 * S + 2:],) * count_moe)

        def pack(lengths, toks, temps, fed, rng, moe, pair=None):
            return jnp.concatenate([
                lengths, toks,
                jax.lax.bitcast_convert_type(temps, jnp.int32), fed,
                jax.lax.bitcast_convert_type(rng, jnp.int32),
                *(m + pair for m in moe)])

        def prefill(params, *args):
            # (params, *scratch, [*pools,] *carry, host): the program of
            # a step that carries prompt. One request's share of the
            # step's budget, a [1, T] tile, through the cached path into
            # its scratch, and behind it in the same sequence one decode
            # row a slot against the pools, so every weight streams once
            # for both; samples the tile's would-be next token (kept only
            # by the prompt's last tile, which writes it, the prompt's
            # length and the temperature into the carry at its slot) and
            # the slots' next tokens. host: live [S] (the slots that
            # decode; none (a step's further spans, an idle engine), and
            # the rows' attention and pool write are skipped, never
            # another shape), pos0, n_real, slot, whether the span ends
            # its prompt, the temperature's bits, how many tile programs
            # were issued before this one, the tile's tokens [T].
            # -> (the carry's tokens [+ counts], slot, *scratch,
            # [*pools,] *carry)
            self.prefill_compile_count += 1    # traces once a tile
            scratch, args = args[:n], args[n:]
            pools, args = (args[:n], args[n:]) if ride else ((), args)
            state, host = args
            live = host[:S] != 0
            pos0, n_real, slot, is_last = (host[S + i] for i in range(4))
            temp = jax.lax.bitcast_convert_type(host[S + 4], jnp.float32)
            tokens = host[None, S + _TILE_HEAD:]
            tile = tokens.shape[1]
            # the tile's padded tail is rows no request owns
            real = (jnp.arange(tile) < n_real)[None, :]
            # the call's key is the carry's with the host's count of tile
            # programs folded in, and the carry's stays (decode splits it):
            # no jitted split on the host, and none here either, since a
            # tile program that handed back a key it had split lost one of
            # a layer's K / V copies from the fast memory (the compiler's
            # memory-space assignment, read off the program compiled for a
            # described v5e; Mistral's tile 24.57 -> 28.19 ms, my chip run,
            # PR 43; PERF.md section 6)
            lengths, toks, temps, fed, rng, moe = unpack(state)
            key = jax.random.fold_in(rng, host[S + 5])
            slots = None
            if ride:
                tokens = jnp.concatenate([tokens, toks[None, :]], axis=1)
                real = jnp.concatenate([real, live[None, :]], axis=1)
                slots = dict(zip(names, pools), idx=lengths,
                             on=jnp.any(live))
                key, sub = jax.random.split(key)
            # the head runs over the rows sampled below and no other: the
            # prompt's would-be next token and the slots' rows behind the
            # tile, 1 + S of T + S
            logits, new, pair = forward(
                params, tokens, scratch, pos0, real=real, slots=slots,
                chunked_prefill=True, logit_rows=jnp.concatenate(
                    [(n_real - 1)[None],
                     jnp.arange(tile, tokens.shape[1], dtype=jnp.int32)]))
            tok = sample_logits_dynamic(
                logits[:, 0], key, temp[None], top_k=top_k,
                top_p=top_p)[0].astype(jnp.int32)
            if ride:
                rows = sample_logits_dynamic(
                    logits[0, 1:], sub, temps, top_k=top_k,
                    top_p=top_p).astype(jnp.int32)
                lengths, toks = lengths + live, jnp.where(live, rows, toks)
            ends = (jnp.arange(S) == slot) & (is_last != 0)
            state = pack(jnp.where(ends, pos0 + n_real, lengths),
                         jnp.where(ends, tok, toks),
                         jnp.where(ends, temp, temps),
                         jnp.where(ends, tok, fed), rng, moe, pair)
            return (state, slot) + new

        def decode(params, *args):
            # (params, *pools, carry, live [S] from the host) -> (carry,
            # *pools). ONE program for the life of the engine: fixed
            # [n_slots] shapes, per-slot idx vector. Python side effect
            # below runs only at trace time — it counts XLA cache misses.
            # The key splits INSIDE the program (the carry keeps its half)
            # so the host does exactly one dispatch per decoded token.
            self.decode_compile_count += 1
            pools, (state, host) = args[:n], args[n:]
            lengths, toks, temps, _, rng, moe = unpack(state)
            live = host != 0
            rng, sub = jax.random.split(rng)
            logits, new, pair = forward(params, toks[:, None], pools,
                                        lengths)
            tok = sample_logits_dynamic(logits[:, -1, :], sub, temps,
                                        top_k=top_k, top_p=top_p)
            state = pack(lengths + live,
                         jnp.where(live, tok.astype(jnp.int32), toks),
                         temps, toks, rng, moe, pair)
            return (state,) + new

        # the pools and the carry are donated. The carry is one array
        # and not four: with four small arrays aliased XLA's memory-space
        # assignment moved one of a layer's K / V copies out of the fast
        # memory (Mistral's decode program 20.13 -> 23.27 ms, my chip run,
        # PR 43; PERF.md section 6), and not aliased each cost the call
        # 75 us; tests/test_chip_compile.py holds both copies there
        self._prefill_fn = jax.jit(
            prefill, donate_argnums=tuple(
                range(1, 2 + n * (2 if ride else 1))))
        self._decode_fn = jax.jit(
            decode, donate_argnums=tuple(range(1, 2 + n)))

    def _new_carry(self, seed: int):
        """The slots' carry with the key of `seed` (`_build_fns` lays it
        out), made where it lives: on the device, replicated on a mesh
        (the step programs hand it back so there, and a first call that
        saw it on one device would make the second one retrace)."""
        import jax
        import jax.numpy as jnp
        S, counts = self.config.n_slots, self._moe_width * self._count_moe

        def carry():
            return jnp.concatenate([
                jnp.zeros((4 * S,), jnp.int32),
                jax.lax.bitcast_convert_type(jax.random.PRNGKey(seed),
                                             jnp.int32),
                jnp.zeros((counts,), jnp.int32)])
        return jax.jit(carry,
                       out_shardings=kv_cache.replicated(self.mesh))()

    def _tile_args(self, tile: int, tokens, pos0: int, slot: int,
                   is_last: bool, temp: float, live) -> np.ndarray:
        """The one array a tile program takes from the host (`prefill`
        in `_build_fns` reads it back): the slots `live` behind the tile,
        the span's `tokens` (padded to `tile`) and where they start, its
        request's slot and temperature, whether it ends the prompt, and
        the count of tile programs issued, which the call's key is made
        of."""
        S = self.config.n_slots
        host = np.zeros((S + _TILE_HEAD + tile,), np.int32)
        host[live] = 1
        host[S:S + _TILE_HEAD] = (
            pos0, len(tokens), slot, is_last,
            np.float32(temp).view(np.int32),
            self.prefill_dispatches & 0x7FFFFFFF)
        host[S + _TILE_HEAD:S + _TILE_HEAD + len(tokens)] = tokens
        return host

    def _compile_prefill_tiles(self):
        """Run every prefill tile on a throwaway scratch, so no request
        is the first user of a shape: ``prefill_compile_count`` reads the
        family's size before the first submit and never moves again. Each
        tile runs twice, on a new scratch and on the one it handed back,
        as a prompt's first and later spans do: on a mesh the two differ
        in sharding and XLA compiles each. The
        slots' pools pass through with no slot live, and the carry with
        no slot written and its key as it was (a tile program draws from
        it and does not advance it); the warm-up's expert rows are
        nobody's."""
        with self._mesh_ctx():
            for tile in self._prefill_tiles:
                host = self._tile_args(
                    tile, np.zeros((tile,), np.int32), 0, 0, False, 0.0, [])
                scratch = self._slots.new_scratch()
                for _ in range(2):
                    scratch = self._call_prefill(scratch, host)[1]
                events.record_instant(
                    "engine.compile", category="engine",
                    trace_id=self._trace_id, fn="prefill", tile=tile,
                    compile_count=self.prefill_compile_count)
        if self._count_moe:
            self._read()
            self._moe_counts[:] = 0

    # -------------------------------------------------------------- intake
    def submit(self, tokens, max_new_tokens: int = 64,
               temperature: Optional[float] = None,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               hold: bool = False) -> RequestHandle:
        """Queue one prompt; returns a streaming RequestHandle.
        deadline_s is relative (seconds from now) — a request still
        queued past it fails with finish_reason='deadline'.
        hold=True parks the request in the queue (FIFO position kept)
        until release_hold() — the remote-prefill hand-off window."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if len(tokens) == 0:
            raise ValueError("empty prompt")
        if len(tokens) > self.config.max_len - 1:
            raise ValueError(
                f"prompt ({len(tokens)} tokens) must leave room to "
                f"decode in a {self.config.max_len}-token slot")
        req = Request(tokens=tokens, max_new_tokens=int(max_new_tokens),
                      temperature=temperature, eos_id=eos_id,
                      deadline_s=(time.monotonic() + deadline_s
                                  if deadline_s is not None else None),
                      trace_ctx=events.current_context())
        with self._work:
            if self._stop:
                raise RuntimeError("engine is stopped")
            h = self.sched.submit(req, hold=hold)
            self._work.notify_all()
        return h

    def release_hold(self, handle: RequestHandle):
        """End a hold-submitted request's hand-off window: it becomes
        admissible on the next step (its imported prefix — if the
        hand-off landed — now matches via the radix trie exactly like a
        locally cached one). Safe to call on any failure path."""
        with self._work:
            self.sched.release_hold(handle.rid)
            self._work.notify_all()

    def begin_drain(self):
        """Preemption drain: refuse new submissions (submit raises and
        the serving layer re-routes), finish everything in flight. The
        loop keeps stepping until the last slot evicts."""
        with self._work:
            self.sched.begin_drain()
            self._work.notify_all()

    # --------------------------------------------------------------- loop
    def start(self) -> "InferenceEngine":
        with self._lock:
            if self._thread is None:
                self._stop = False
                self._thread = threading.Thread(
                    target=self._loop, name="inference-engine", daemon=True)
                self._thread.start()
        return self

    def stop(self):
        with self._work:
            self._stop = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        with self._lock:
            self.sched.fail_all(RuntimeError("engine stopped"))
            self._deliver()

    def _loop(self):
        while True:
            with self._work:
                if self._stop:
                    return
                if not self.sched.has_work():
                    # deadline sweeps still need an occasional wake
                    self._work.wait(timeout=0.05)
                    if self._stop:
                        return
            try:
                self.step()
            except Exception as e:           # engine must not die silently
                with self._lock:
                    self.sched.fail_all(e)
                    self._deliver()

    # --------------------------------------------------------------- step
    def step(self) -> bool:
        """One engine iteration. Between two step programs the engine's
        thread does only what the next program needs, so a step runs in
        this order:

        - plan: reap cancels/deadlines, evict full slots, spend the
          prefill budget. It is planned after the step before it has been
          read, so an arrival joins the very next plan.
        - dispatch: everything the step runs, back to back, no call
          waiting for a value. ONE program where the plan holds one span:
          the span's prefill tile and, behind it in the same sequence, a
          decode row for every occupied slot (admission and decode share
          each weight's read; a step's further spans run the same program
          with no slot live). A step with no prompt to prefill runs the
          decode program. A request whose prompt ends in this step
          decodes from the next. (The other shape, a model with an
          indexer: the tile programs, then right behind them the decode
          program for every occupied slot, the new request among them.)
          The slots' CARRY (lengths, last tokens, temperatures, the
          key; `_build_fns`) lives on the device
          and only the programs write it; the host says what it alone
          knows in one packed array a program (the live mask, which is
          how an eviction or a cancel reaches the carry, and a tile's
          tokens, place, slot and temperature) and keeps a mirror of the
          lengths. With a temperature above zero the key's stream is the
          carry's: the decode program splits it where it runs, a tile
          program draws with the carry's key and the host's count of
          tile programs folded in; no program of the key's runs on the
          host's side of a step.
        - deliver: under the programs now running, what the step BEFORE
          decided is handed over: tokens to their consumers' queues,
          finishes to the handles, the recorder's spans ended.
        - read: once, the last program's output, which holds every token
          the step sampled.
        - decide: each live request's token is appended and its finish
          decided (EOS, max_new_tokens; `max_len` at the next plan), slots
          are freed. What was decided is held for the next step's deliver;
          where no work follows it is delivered at once.

        Returns True if any device work ran."""
        with self._lock:
            ph = self._phases
            now = time.monotonic()
            ph.begin(self.steps, now)           # plan
            try:
                return self._step(ph, now)
            finally:
                ph.end()

    def _step(self, ph: _StepPhases, now: float) -> bool:
        sched = self.sched
        ahead = sched.holding()     # the step before left tokens held
        for st in sched.reap(now):
            self._slots.scratch.pop(st.rid, None)
        # capacity eviction BEFORE the step: a full slot has nowhere
        # to write its next token
        for st in sched.active_states():
            if self._lengths[st.slot] >= self.config.max_len:
                sched.evict(st, FINISH_LENGTH)
        active = sched.active_states()
        spans = self._prefill_spans(sched.plan_prefill())
        # the decode rows ride in the step's FIRST tile program
        ride = self._ride and bool(spans)
        for i, span in enumerate(spans):
            self._issue_prefill(span, now, active if ride and i == 0 else ())
        ended = [span.state for span in spans if span.is_last]
        rows = [(st, st.slot) for st in active]
        if not ride:
            # a prompt that ended in this step decodes in it: its first
            # token is in the carry (unless one token is all it may have)
            rows += [(st, st.slot) for st in ended
                     if st.request.max_new_tokens > 1]
        slots = [slot for _, slot in rows]
        if rows and not ride:
            self._issue_rows(slots)
        did = bool(spans or rows)
        self.issued_ahead += did and ahead
        # under the programs now running: what the step before decided,
        # then what of this step waits for no token
        ph.enter("emit")
        self._deliver()
        dspan = None
        if rows:
            self.fused_steps += ride
            # decode is a BATCH phase: when one request occupies the
            # engine its span adopts that request's trace (the
            # acceptance path — one Serve call renders its decode
            # windows inline); with several co-resident traces the
            # span records under the engine's own root trace with
            # slot attribution instead of picking a favorite. It is
            # the step's one record: it starts where the step did and
            # ends with the step's phases on it
            traces = {st.span.trace_id for st, _ in rows
                      if st.span is not None}
            if len(rows) == 1 and rows[0][0].span is not None:
                d_trace = rows[0][0].span.trace_id
                d_parent = rows[0][0].span.span_id
            elif len(traces) == 1:
                d_trace, d_parent = next(iter(traces)), None
            else:
                d_trace, d_parent = self._trace_id, None
            dspan = events.start_span(
                "engine.decode", category="engine",
                trace_id=d_trace, parent_span_id=d_parent,
                start=ph.t_wall,
                step=self.steps, slots_active=len(rows),
                slots_occupied=sched.occupancy(),
                queue_depth=sched.queue_depth())
            self._rows_ran(slots)
        n_emitted = 0
        if rows or ended:
            ph.enter("read")
            toks, fed = self._read()
            ph.enter("emit")
            now = time.monotonic()
            for st in ended:
                # the prompt's first token: where its tile wrote it, and
                # what a decode program behind the tile consumed
                self.first_tokens += 1
                self.prefill_span_s += now - st.admitted_t
                sched.prefill_done(st, int(fed[st.slot]), now)
            for st, slot in rows:
                if st.slot is None:
                    continue     # its first token ended it
                self.tokens_generated += 1
                n_emitted += 1
                sched.decode_emit(st, int(toks[slot]), now)
        self.steps += 1
        if self.on_step is not None:
            try:
                self.on_step({"slots_occupied": sched.occupancy(),
                              "queue_depth": sched.queue_depth()})
            except Exception:
                pass
        ph.end(dspan, self._after, tokens=n_emitted)
        if not sched.has_work():
            self._deliver()         # nothing follows to deliver behind
        return did

    def _deliver(self):
        """Hand over what was decided and held: tokens and finishes
        (`Scheduler.deliver`), then the recorder's spans."""
        self.sched.deliver()
        after, self._after = self._after, []
        for fn, kw in after:
            fn(**kw)

    def _read(self):
        """The step's one read, the carry as the last program left it ->
        (the slots' last tokens, the tokens decode last consumed: a
        prompt's first). The expert counts at its end are added to the
        host's totals (the carry's sum wraps at 32 bits; so does the
        difference)."""
        host, S = np.asarray(self._carry), self.config.n_slots
        if self._count_moe:
            seen = host[-self._moe_width:].view(np.uint32)
            self._moe_counts += seen - self._moe_seen
            self._moe_seen = seen
        return host[S:2 * S], host[3 * S:4 * S]

    def _rows_ran(self, slots):
        """The decode rows of `slots` were issued: what the counters make
        of their lengths, and the mirror of them."""
        read = self._rows_read_of([int(n) for n in self._lengths[slots]])
        for key, n in read.items():
            self._rows_read[key] += n
        self._lengths[slots] += 1

    def _issue_rows(self, slots):
        """Issue the decode program for the rows of `slots`."""
        self._phases.enter("dispatch")
        live = np.zeros((self.config.n_slots,), np.int32)
        live[slots] = 1
        compiles0 = self.decode_compile_count
        with self._mesh_ctx():
            self._carry, *new = self._decode_fn(
                self.params, *self._slots.pools(), self._carry, live)
            self._slots.rebind(new)
        if self.decode_compile_count > compiles0:
            # a decode retrace is THE perf cliff this engine is
            # built to avoid — make every occurrence a first-class
            # timeline event (tests assert the count stays at 1)
            events.record_instant(
                "engine.compile", category="engine",
                trace_id=self._trace_id, fn="decode",
                compile_count=self.decode_compile_count)

    def _prefill_spans(self, chunks: List[PrefillChunk]):
        """The step's plan, one piece a dispatch: the consecutive chunks
        the scheduler gave one request are one span of its prompt, up to
        the largest compiled tile (a budget raised past it at run time
        makes more dispatches, never a new shape). The scheduler knows
        that tile and gives a prompt that does not end in the step whole
        tiles of it, so every span here is a whole tile or ends its
        prompt."""
        cap = self._prefill_tiles[-1]
        spans: List[PrefillChunk] = []
        for ch in chunks:
            last = spans[-1] if spans else None
            if (last is not None and last.state is ch.state
                    and last.start + last.length == ch.start
                    and last.length + ch.length <= cap):
                spans[-1] = PrefillChunk(
                    state=ch.state, start=last.start,
                    length=last.length + ch.length, is_last=ch.is_last)
            else:
                spans.append(ch)
        return spans

    def _tile_of(self, prompt_len: int) -> int:
        """The tile every span of a prompt runs in: the smallest that
        holds the longest span a step can give it, its tail padded
        (``n_real`` selects the logits row). Chosen from the prompt and
        not from the span: a prompt's spans are whole tiles from where
        its prefill starts (the cut is the prompt's own, whatever else
        the step's budget went to: ``Scheduler.plan_prefill``), but a
        prefix hit moves that start and a prompt's last span is as long
        as what is left, and two tiles are two programs whose sums XLA
        may order differently, so a tile chosen by the span would let a
        prefix hit change a prompt's K/V in a last bit and with it, at a
        near-tie of logits or of an MoE router, its greedy tokens."""
        tiles = self._prefill_tiles
        return next(t for t in tiles if t >= min(prompt_len, tiles[-1]))

    def _call_prefill(self, scratch, host):
        """One call of the tile program on the host's array `host`
        (`_tile_args`) -> (the span's slot, the scratch), on the device.
        The carry and, where decode rows ride behind the tile, their
        pools are rebound here."""
        n = len(scratch)
        pools = self._slots.pools() if self._ride else ()
        self._carry, slot, *new = self._prefill_fn(
            self.params, *scratch, *pools, self._carry, host)
        if self._ride:
            self._slots.rebind(new[n:])
        return slot, tuple(new[:n])

    def _issue_prefill(self, ch: PrefillChunk, now: float, active=()):
        """Issue one span of a prompt in its tile, with the decode rows of
        the `active` states' slots behind it. Nothing here waits for a value: a
        span that ends its prompt has its slot made (the scratch copied
        in) behind its tile, and its first token is the carry's until the
        step reads. `now`: the step's start (time.monotonic())."""
        ph = self._phases
        ph.enter("dispatch")
        st = ch.state
        if st.span is None:
            # first span == admission: open the engine-slot span, at the
            # step's start, where `now` was read. It
            # parents under the submitting request's propagated context
            # (Serve path) or roots its own trace (direct engine use),
            # and carries the queue-wait the built-in scheduler-latency
            # metric is derived from.
            ctx = st.request.trace_ctx
            st.admitted_t = now
            wait_s = now - st.handle.submitted_t
            self.admitted += 1
            self.queue_wait_s += wait_s
            st.span = events.start_span(
                "engine.slot", category="engine",
                trace_id=ctx[0] if ctx else None,
                parent_span_id=ctx[1] if ctx else None,
                start=ph.t_wall, rid=st.rid, slot=st.slot,
                prompt_tokens=len(st.request.tokens),
                queue_wait_ms=round(wait_s * 1e3, 3))
        scratch = self._slots.scratch.get(st.rid)
        if scratch is None:
            scratch = self._slots.new_scratch()
            if st.prefix_nodes:
                # radix hit: the matched span's KV comes out of the
                # block store as device-side copies — no forward pass
                # runs over [0, prefix_matched)
                scratch = self._restore_prefix(st, scratch)
        prompt = st.request.tokens
        tile = self._tile_of(len(prompt))
        slots = [a.slot for a in active]
        host = self._tile_args(
            tile, prompt[ch.start:ch.start + ch.length], ch.start, st.slot,
            ch.is_last, st.temperature, slots)
        pspan = events.start_span(
            "engine.prefill", category="engine",
            trace_id=st.span.trace_id, parent_span_id=st.span.span_id,
            rid=st.rid, slot=st.slot, offset=ch.start, length=ch.length,
            tile=tile, is_last=ch.is_last,
            live=ch.start + ch.length,    # positions the tile attended
            decode_rows=len(active),      # slots advanced in its program
            slots_occupied=self.sched.occupancy())
        compiles0 = self.prefill_compile_count
        self.prefill_dispatches += 1
        self.prefill_tokens += ch.length
        layers, kernel = self._tile_layers[tile]
        self.tile_attn_layers += layers
        self.tile_kernel_layers += kernel
        with self._mesh_ctx():
            slot, scratch = self._call_prefill(scratch, host)
        if self._has_tail:
            rows = tile + (self.config.n_slots if self._ride else 0)
            self.tile_rows += rows
            self.tail_rows_run += self._tail_rows[rows]
        if self.prefill_compile_count > compiles0:
            events.record_instant(
                "engine.compile", category="engine",
                trace_id=st.span.trace_id,
                parent_span_id=pspan.span_id, fn="prefill",
                compile_count=self.prefill_compile_count)
        self._after.append((pspan.end, {"end": time.time()}))
        if ch.is_last:
            if self.prefix_cache is not None:
                self._populate_prefix(st, scratch)
            self._slots.insert(scratch, slot)
            self._slots.scratch.pop(st.rid, None)
            self._lengths[st.slot] = len(prompt)
        else:
            if self._write_through:
                scratch = self._publish_chunk(st, scratch, ch)
            self._slots.scratch[st.rid] = scratch
            self.sched.advance_prefill(st, ch.length)

    # ------------------------------------------------------- prefix cache
    def _restore_prefix(self, st, scratch):
        """Copy the matched trie blocks into this request's scratch
        cache ([0, prefix_matched) chunk by chunk), then unpin them.
        Runs once, on the request's first prefill chunk, under the
        engine lock — eviction cannot race the copies."""
        C = self.config.prefill_chunk
        for i, node in enumerate(st.prefix_nodes):
            scratch = self._blocks.load(scratch, node.block, i * C)
        events.record_instant(
            "engine.prefix_hit", category="engine",
            trace_id=st.span.trace_id if st.span else None,
            parent_span_id=st.span.span_id if st.span else None,
            rid=st.rid, slot=st.slot, matched_tokens=st.prefix_matched,
            prompt_tokens=len(st.request.tokens))
        self.sched.unpin_prefix(st)
        return scratch

    def _populate_prefix(self, st, scratch):
        """Miss path, at prefill completion: extend the trie over every
        full chunk of the prompt and fill the newly allocated blocks
        from scratch (already-present chunks are skipped — their KV is
        identical by construction)."""
        for off, block in self.prefix_cache.insert(st.request.tokens):
            self._blocks.save(scratch, block, off)

    def _publish_chunk(self, st, scratch, ch):
        """Write-through miss path (a store whose blocks are not the
        computed values: int8), non-final spans: publish each COMPLETED
        full chunk as it finishes, then reload what the store holds into
        this request's OWN scratch — the miss attends exactly the
        numbers a later prefix-cache hit will restore, so greedy output
        is bit-identical hit vs miss. The final chunk (full or padded)
        is save-only in _populate_prefix: the admission match is capped
        one token short of the prompt, so no hit ever restores it and
        both paths attend it raw."""
        end = ch.start + ch.length
        for off, block in self.prefix_cache.insert(st.request.tokens[:end]):
            self._blocks.save(scratch, block, off)
            scratch = self._blocks.load(scratch, block, off)
        return scratch

    # --------------------------------------------------- disagg hand-off
    def export_kv_blocks(self, tokens, max_chunks: Optional[int] = None):
        """Sender half of the prefill/decode hand-off: copy the cached
        KV blocks covering ``tokens``' chunk-aligned prefix out of the
        block pool as host arrays. Returns ``(covered_tokens, spans)``
        where each span is one block in the store's format (kv_cache.py:
        ``(k, v)`` of ``[n_layers, 1, prefill_chunk, Hkv, D]``, or the
        int8 values and their scale rows) — the unit serve/disagg.py
        frames onto the data plane. Defaults to the
        admission cap (one token short of the prompt) so the importing
        engine's match covers exactly what its scheduler would use.
        Blocks stay pinned for the duration of the copy; compile-once
        holds (one fixed-shape export program)."""
        if self.prefix_cache is None:
            return 0, []
        C = self.config.prefill_chunk
        cap = (max(0, len(tokens) - 1) // C if max_chunks is None
               else max(0, int(max_chunks)))
        with self._lock:
            nodes = self.prefix_cache.walk(tokens, cap)
            try:
                spans = [self._blocks.export(n.block) for n in nodes]
            finally:
                self.prefix_cache.release(nodes)
            if spans:
                self.kv_exports += 1
        return len(spans) * C, spans

    def import_kv_blocks(self, tokens, spans) -> int:
        """Receiver half: land remotely prefilled spans in this engine's
        block pool and extend the trie over them, so the NEXT admission
        of ``tokens`` (or any prompt sharing the prefix) hits via the
        ordinary load_span path — no forward pass runs over the imported
        range, and greedy output is bit-identical to a local prefill
        (the blocks are the same deterministic computation, just done
        elsewhere). Chunks already cached locally are skipped; returns
        the number of prompt tokens newly covered."""
        if self.prefix_cache is None or not spans:
            return 0
        C = self.config.prefill_chunk
        n = min(len(spans), len(tokens) // C)
        if n <= 0:
            return 0
        with self._lock:
            created = self.prefix_cache.insert(
                [int(t) for t in tokens[:n * C]])
            for off, block in created:
                self._blocks.import_span(spans[off // C], block)
            imported = len(created) * C
            if imported:
                self.kv_imports += 1
                self.remote_prefix_tokens += imported
        return imported

    def kv_import_is_exact(self, span) -> bool:
        """Whether importing a peer's span (one item of what
        ``export_kv_blocks`` returned there) gives this engine the blocks
        its own prefill would have saved (BlockStore.imports_exactly)."""
        return (self._blocks is not None
                and self._blocks.imports_exactly(span))

    # -------------------------------------------------------------- stats
    def stats(self) -> Dict:
        out = {
            "n_slots": self.config.n_slots,
            "slots_occupied": self.sched.occupancy(),
            "slots_free": self.config.n_slots - self.sched.occupancy(),
            "queue_depth": self.sched.queue_depth(),
            "active": len(self.sched.active_slots()),
            "steps": self.steps,
            "fused_steps": self.fused_steps,
            "issued_ahead": self.issued_ahead,
            "tokens_generated": self.tokens_generated,
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_deferred": self.sched.prefill_deferred,
            "prefill_tokens": self.prefill_tokens,
            "admitted": self.admitted,
            "queue_wait_s": self.queue_wait_s,
            "first_tokens": self.first_tokens,
            "prefill_span_s": self.prefill_span_s,
            "decode_compile_count": self.decode_compile_count,
            "draining": self.sched.draining,
        }
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
            out["kv_exports"] = self.kv_exports
            out["kv_imports"] = self.kv_imports
            out["remote_prefix_tokens"] = self.remote_prefix_tokens
        out.update(kv_cache.format_stats(
            self._kv_quant, self.model.cfg.head_dim, self._kv_itemsize))
        out.update(self._pool_bytes)
        if any(layers for layers, _ in self._tile_layers.values()):
            out["tile_attn_layers"] = self.tile_attn_layers
            out["tile_kernel_layers"] = self.tile_kernel_layers
        out.update(self._rows_read)
        if self._has_tail:
            out["tile_rows"] = self.tile_rows
            out["tail_rows_run"] = self.tail_rows_run
        if self._count_moe:
            out["moe_rows_computed"] = int(self._moe_counts[0])
            out["moe_local_picks"] = int(self._moe_counts[1])
            if self._moe_width == 4:
                out["moe_rows_hit"] = int(self._moe_counts[2])
                out["moe_rows_real"] = int(self._moe_counts[3])
        # how this process started: its launch phases (the newest of
        # each) and the compile watch's totals, cumulative for the process
        for phase, (t_mono, seconds) in events.launch_phases().items():
            if phase == "callable_init":
                out["startup_t_mono"] = t_mono
            if phase in _STARTUP_KEYS:
                out[_STARTUP_KEYS[phase]] = seconds
        out.update(("xla_" + key, value)
                   for key, value in compile_cache.totals().items())
        return out
