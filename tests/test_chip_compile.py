"""The main path's kernels, compiled for a described (not attached) v5e
chip at the shapes tpu-1b really produces. Nothing runs: this guards what
the TPU compiler would refuse (tiling, VMEM, partitioning) at no chip time.

All of it lives in this one file and in fixtures: only the xdist worker
that is handed the file loads the TPU library, and it compiles in its own
process with the persistent compile cache off (an entry written for a
described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# [B*H, L, 128] as ops/attention.py lays a batch out for the kernel
CORE_SHAPES = {
    "tpu-1b-train-B8-H16-L1024": (128, 1024, 128),
    "tpu-1b-max_seq_len-4096": (32, 4096, 128),
    "tpu-1b-shard-of-fsdp2xtensor2": (32, 1024, 128),
}


@pytest.mark.parametrize("mode", ["fwd", "grad"])
@pytest.mark.parametrize("shape", list(CORE_SHAPES.values()),
                         ids=list(CORE_SHAPES))
def test_flash_core_compiles_for_v5e(one_chip, no_compile_cache, shape,
                                     mode):
    from ray_tpu.ops.attention import _flash_core
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def core(q, k, v):
        return _flash_core(True, None, shape[-1] ** -0.5, False, q, k, v)

    fn = core if mode == "fwd" else jax.grad(
        lambda q, k, v: core(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    _compile(fn, x, x, x)


@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_head_dim_64_pads_to_128_and_compiles(one_chip, no_compile_cache,
                                              mode):
    """The llama-* layout (head_dim 64, GQA 4:1) takes the pad-to-128
    branch of flash_attention; asked for by name it is the kernel."""
    from ray_tpu.ops.dispatch import attention
    q = jax.ShapeDtypeStruct((2, 1024, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 1024, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def att(q, k, v):
        return attention(q, k, v, causal=True, impl="flash")

    fn = att if mode == "fwd" else jax.grad(
        lambda q, k, v: att(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    _compile(fn, q, kv, kv)


def test_flash_by_name_shards_over_fsdp2_tensor2(topo, no_compile_cache):
    """impl="flash" under a 2x2 mesh is the kernel per (batch, head)
    shard inside shard_map, partitioned for four described chips."""
    from ray_tpu.ops.dispatch import attention
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh, use_mesh
    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=topo.devices)
    spec = NamedSharding(mesh, P(("data", "fsdp"), None, "tensor", None))
    x = jax.ShapeDtypeStruct((8, 1024, 16, 128), jnp.bfloat16,
                             sharding=spec)

    def loss(q, k, v):
        return attention(q, k, v, causal=True,
                         impl="flash").astype(jnp.float32).sum()

    with use_mesh(mesh):
        _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


# (layers, slots, positions, KV heads, query heads) of a cell's pools
POOL_SHAPES = {
    "keye-vl-2.0-longdoc-mixed": (16, 8, 17408, 4, 32),
    "mistral-7b-chat-steady": (20, 16, 2048, 8, 32),
}


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("shape", list(POOL_SHAPES.values()),
                         ids=list(POOL_SHAPES))
def test_pool_decode_kernel_takes_the_pool_as_it_lies(one_chip,
                                                      no_compile_cache,
                                                      shape, masked):
    """ops/decode_attention.py at the shapes of Keye-VL-2.0's cell (8
    slots of 17,408 positions, 4 KV heads of 128, 16 layers) and of
    Mistral-7B's (16 slots of 2,048, 8 KV heads, 20 layers): the kernel
    compiles, and its view of a pool as [.., M * Hkv, D] rows is a bitcast
    of the parameter, never a copy of a pool (285 MB a layer at the
    first)."""
    import re

    from ray_tpu.ops import decode_attention as da
    n, B, M, Hkv, H = shape
    assert da.fits(M, Hkv, 128)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((n, B, M, Hkv, 128), jnp.bfloat16)
    args = [s((B, H, 128), jnp.bfloat16), pool, pool, s((), jnp.int32),
            s((B,), jnp.int32)] + [s((B, M), jnp.bool_)] * masked
    text = _compile(da.pool_decode_attention, *args).as_text()
    assert not re.search(rf"= bf16\[{n},{B},[\d,]+\]\S* copy\(", text)
    assert re.search(rf"bf16\[{n},{B},{M * Hkv},128\]\S* bitcast\(", text)


def _step_program(config, program, one_chip, monkeypatch):
    """(the engine, its "decode" or "tile" program compiled)
    at a benchmark configuration's own size, for a described v5e. The
    engine is built on shapes: nothing is allocated and nothing runs. The
    four predicates that ask for the backend (`_kernel_reads` and
    `_latent_row_kernel_takes`, a decode row's pool kernels;
    `_tile_kernel_takes` and `_latent_tile_kernel_takes`, a tile's flash
    kernels) see the CPU's here: they are made to answer as on the chip,
    so that the program compiled is the one the cell runs; so is the
    expert layer's `moe._kernel_takes`, the grouped form's kernel."""
    import numpy as np
    from flax.core import meta

    from perfbench import spec
    from ray_tpu.inference import kv_cache
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import moe, sparse_attention
    from ray_tpu.ops import decode_attention, grouped_matmul, tile_attention
    cfg = spec.load_config(spec.load_benchmark(), config)
    family = spec.family_of(cfg)
    model = family.build_model(family.model_kwargs(cfg))
    params = meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    monkeypatch.setattr(kv_cache, "zeros", lambda shape, dtype, sh=None:
                        jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype)))
    monkeypatch.setattr(InferenceEngine, "_compile_prefill_tiles",
                        lambda self: None)
    monkeypatch.setattr(sparse_attention, "_kernel_reads",
                        decode_attention.fits)
    monkeypatch.setattr(sparse_attention, "_tile_kernel_takes",
                        tile_attention.fits)
    monkeypatch.setattr(sparse_attention, "_latent_tile_kernel_takes",
                        tile_attention.latent_fits)
    monkeypatch.setattr(sparse_attention, "_latent_row_kernel_takes",
                        decode_attention.latent_fits)
    monkeypatch.setattr(moe, "_kernel_takes", grouped_matmul.fits)
    engine = dict(cfg["engine"], prefix_cache_slots=0)
    del engine["max_ongoing_requests"]
    eng = InferenceEngine(model, params, EngineConfig(**engine))
    S, tile = eng.config.n_slots, eng._prefill_tiles[-1]
    if program == "decode":
        fn, args = eng._decode_fn, (
            eng.params, *eng._slots.pools(), eng._carry,
            np.zeros((S,), np.int32))
    else:
        fn, args = eng._prefill_fn, (
            eng.params, *eng._slots.new_scratch(), *eng._slots.pools(),
            eng._carry, eng._tile_args(
                tile, np.zeros((tile,), np.int32), 0, 0, False, 0.0, []))
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype, sharding=one_chip), args)
    return eng, fn.lower(*args).compile()


def _step_program_text(config, program, one_chip, monkeypatch):
    eng, compiled = _step_program(config, program, one_chip, monkeypatch)
    return eng, compiled.as_text()


@pytest.mark.parametrize("program", ["decode", "tile"])
def test_step_programs_keep_a_layers_kv_in_fast_memory(
        one_chip, no_compile_cache, monkeypatch, program):
    """The engine's step programs at `mistral-7b.chat-steady`'s size (20
    layers, 16 slots of 2048): a decode row attends K and V where they lie
    in the slots' pools, so NO layer of a pool ([16, 2048, 8, 128], 67 MB)
    is sliced out or copied, and no pool is; the pools reach the kernel of
    ops/decode_attention.py as a bitcast of the program's own parameters.
    (Until PR 47 each layer's K and V were copied out of the pool whole,
    15% of the cell's device time, and this test held both copies to the
    fast memory, `S(1)` in the compiled text: with the slots' carry
    donated, or with the tile program handing back a key it had split, the
    compiler's memory-space assignment left one of the two in HBM and the
    attention's ops ran 1.6 times as long; `decode_prog_ms` 20.13 -> 23.27,
    `prefill_prog_ms` 24.57 -> 28.19, my chip run, PR 43; PERF.md section
    6. With no copy there is nothing to drop.) The dense model's tile
    keeps its own form (`_cached_attention` against its scratch): no tile
    kernel in either program."""
    import re
    eng, text = _step_program_text("mistral-7b", program, one_chip,
                                   monkeypatch)
    assert eng._slots.shapes["k"] == (20, 16, 2048, 8, 128)
    # no op makes a layer of a pool, and none copies a pool
    assert not re.findall(r"= bf16\[16,2048,8,128\]", text)
    assert not re.findall(r"= bf16\[20,16,2048,8,128\]\S* copy\(", text)
    # K's pool and V's as the kernel's rows, each a bitcast
    views = re.findall(r"= bf16\[20,16,16384,128\]\S* (\w+)\(", text)
    assert views == ["bitcast", "bitcast"], views
    assert text.count("tpu_custom_call") == 1 and re.search(
        r"%pool_decode_attention\S* = .* custom-call\(", text)


TILE_KERNEL_CELLS = {
    # (layers that attend, those of them by position, KV heads, G,
    # positions of the scratch by position): Trinity S S F S S (four rings
    # beside the one cache by position), Falcon-H1's six
    "trinity-large-preview": (5, 1, 8, 6, 26624),
    "falcon-h1-34b": (6, 6, 4, 5, 9216),
}


@pytest.mark.parametrize("config", list(TILE_KERNEL_CELLS))
def test_tile_programs_attend_through_the_flash_kernel(
        one_chip, no_compile_cache, monkeypatch, config):
    """The tile programs of `trinity-large-preview.longdoc-report` (1,024
    rows; four rings of 5,120 places under a window of 4,096 and one cache
    of 26,624 positions; 48 heads over 8) and `falcon-h1-34b.rag-answer`
    (1,024 rows against 9,216 positions, 20 heads over 4, six layers) at
    the cells' own sizes: every layer that attends holds the kernel of
    ops/tile_attention.py ONCE, which is what the engine's counters say
    (`tile_attn_layers` / `tile_kernel_layers` a dispatch), and the XLA
    loop's float32 carry `[1, Hkv, G, 1024, 128]`, which went through HBM
    every key block, is in no op. A layer's K and V of the scratch by
    position reach the kernel as `[M, Hkv * 128]`, one relayout each (the
    scratch is tiled over (Hkv, 128)) with the tile's rows written into
    it, and are copied no second time (written into the scratch first,
    Trinity's 54 MB layer was copied twice more for K and for V, 0.68 ms a
    tile: my chip run, PR 48). The decode rows that ride behind the tile
    keep the pool kernel, once a layer that attends."""
    import re
    layers, by_position, Hkv, G, M = TILE_KERNEL_CELLS[config]
    eng, text = _step_program_text(config, "tile", one_chip, monkeypatch)
    assert eng._tile_layers == {1024: (layers, layers)}
    assert len(re.findall(r"%tile_attention\S* = .* custom-call\(",
                          text)) == layers
    assert len(re.findall(r"%pool_decode_attention\S* = .* custom-call\(",
                          text)) == layers
    assert text.count("tpu_custom_call") == 2 * layers
    # (Trinity's experts hold 32 of 256 rows a group: the dense dispatch)
    assert "grouped_swiglu" not in text
    assert f"f32[1,{Hkv},{G},1024,128]" not in text
    assert len(re.findall(rf"= bf16\[{M},{Hkv * 128}\]\S* fusion\(",
                          text)) == 2 * by_position
    assert not re.search(rf"= bf16\[(1,)*{M},{Hkv},128\]\S* copy\(", text)


@pytest.mark.parametrize("program", ["decode", "tile"])
def test_latent_step_programs_read_the_pool_where_it_lies(
        one_chip, no_compile_cache, monkeypatch, program):
    """The step programs of `sarvam-105b.longdoc-answer` (8 layers of
    latent attention, 16 slots of 18,432 positions, 576 values a position a
    layer): the pool keeps its positions LAST and is in ONE layout through
    the whole program, never copied and no layer of it sliced out, and so
    is the slots' pool behind a tile, whose write stands under no `cond`.
    (With the positions before the 576 values the compiler relaid the whole
    pool, 2.7 GB, at each end of both programs, to have a key block's
    positions in the lanes for the decode row's two products; with the
    slots' write in a branch it relaid it for the write: temp 3.04 GB
    against 0.02 and 0.38, read off these compiles, PR 50.) No array holds
    the keys of a whole scratch. The DECODE ROWS read the pool through the
    latent kernel of ops/decode_attention.py once a layer, alone and behind
    a tile: eight calls in the decode program and eight more in the tile
    program, whose sixth operand is the program's own parameter, the pool
    whole and row-major (no `copy` or `transpose` makes it); no `while` is
    left under `mla_row` (the XLA loop over key blocks, eight of them in
    either program before PR 52) and no op outside the kernel makes a key
    block's scores or exponentials `[16, 64, 512]` float32. The decode
    program's temporaries are 29.8 MB where the loop's were 16.6 (the
    eight calls' outputs, 3 MB a layer; read off this compile, PR 52), the
    tile program's 0.258 GB where the loop's rows left 0.254. The
    TILE goes through the latent kernel of ops/tile_attention.py once a
    layer, which is what the engine's counters say (8 of 8): the layer of
    the scratch reaches it as `[576, 19456]`, row-major like the scratch
    itself and copied by no op (the loop relaid each layer twice a tile,
    `{2,3,1,0}` and `{1,2,0}`, 2 x 22 MB), the loop's float32 score block
    `[64, 1024, 512]` (134 MB, through HBM three times a key block) and
    its carry `[1, 64, 1, 1024, 128]` are in no op, and the program's
    temporaries are no more than the loop's 0.384 GB (0.292, read off this
    compile, PR 51)."""
    import re
    eng, compiled = _step_program("sarvam-105b", program, one_chip,
                                  monkeypatch)
    text = compiled.as_text()
    assert eng._slots.shapes == {"lat": (8, 16, 576, 18432)}
    assert eng._tile_layers == {1024: (8, 8)}
    assert not re.findall(r"= bf16\[16,576,18432\]", text)
    assert not re.findall(r"= bf16\[8,16,576,18432\]\S* copy\(", text)
    # (the second: the row kernel's constraint on its operand, row-major too)
    assert set(re.findall(r"bf16\[8,16,576,18432\](\{[^}]*\})", text)) \
        == {"{3,2,1,0:T(8,128)(2,1)}", "{3,2,1,0}"}
    assert not re.findall(r"\[(?:1,)?19456,64,(?:128|192|256)\]", text)
    kernels = re.findall(r"%latent_tile_attention\S* = .* custom-call\(",
                         text)
    rows = re.findall(
        r"%latent_pool_decode_attention\S* = .* custom-call\(([^)]*)\)", text)
    pool = re.search(r"%(\S+) = bf16\[8,16,576,18432\]\S* parameter\(",
                     text).group(1)
    assert len(rows) == 8
    assert all(ops.split(", ")[5].split("*/")[-1] == "%" + pool
               for ops in rows), rows
    assert not re.findall(r"mla_row/\S*while", text)
    # (its experts hold a part of a group's rows: the dense dispatch)
    assert "grouped_swiglu" not in text
    assert not re.findall(
        r"= f32\[16,64,512\]\S* (?:exponential|convolution)\(", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode":
        assert text.count("tpu_custom_call") == 8 and temp <= 32_000_000
        return
    assert len(kernels) == 8 and text.count("tpu_custom_call") == 16
    assert not re.findall(r"f32\[(?:1,)*64,(?:1,)?1024,(?:512|128)\]", text)
    layouts = set(re.findall(r"bf16\[(?:\d+,)*576,19456\]\{([\d,]+)", text))
    assert layouts <= {"1,0", "2,1,0", "3,2,1,0"}, layouts
    assert not re.findall(
        r"= bf16\[(?:\d+,)*576,19456\]\S* (?:copy|transpose)\(", text)
    assert temp <= 384_121_344


@pytest.mark.parametrize("program", ["decode", "tile"])
def test_paired_pools_are_read_and_written_where_they_lie(
        one_chip, no_compile_cache, monkeypatch, program):
    """The step programs of `phi-4-mini-flash-reasoning.reason-longctx` (32
    unrolled layers of five kinds, 16 slots of 12,288 positions): K and V
    are kept by PAIR of heads, 10 pairs of 128 a position, which is no
    whole sublane tile, so the chip keeps the pools positions-minor with
    the heads outside them and whatever wants them row-major makes it
    copy a pool WHOLE. No op does: the decode rows read the ONE cache by
    position (eight layers) and the eight rings through
    `diff_attention.row_attention`'s loop over the five axes (the pool
    kernel's matrix view copied both, temp 1.55 GB a decode step, and the
    kind does not call it), their rows are written a
    slot at a time and not by a scatter (which relaid the rings, 1 GB in
    and out), and the slots' writes behind a tile stand under no `cond`
    (the branch relaid the one cache, 2 x 1 GB): read off these compiles,
    PR 53. The tile's nine layers that attend go through the flash kernel
    of ops/tile_attention.py on the padded queries."""
    import re
    eng, compiled = _step_program("phi-4-mini-flash-reasoning", program,
                                  one_chip, monkeypatch)
    text = compiled.as_text()
    assert eng._slots.shapes == {
        "k": (1, 16, 12288, 10, 128), "v": (1, 16, 12288, 10, 128),
        "wk": (8, 16, 1536, 10, 128), "wv": (8, 16, 1536, 10, 128),
        "s": (9, 16, 16, 5120), "c": (9, 16, 3, 5120)}
    assert eng._tile_layers == {1024: (9, 9)}
    # no op copies a pool, a layer of one, or the pool as a matrix of rows
    assert not re.findall(
        r"= bf16\[(?:1|8),16,(?:12288|1536),10,128\]\S* copy\(", text)
    assert not re.findall(r"= bf16\[16,(?:12288|1536),10,128\]", text)
    assert not re.findall(r"= bf16\[(?:1|8),16,(?:122880|15360),128\]", text)
    assert not re.findall(r"= f32\[9,16,16,5120\]\S* copy\(", text)
    assert not re.findall(r"%pool_decode_attention\S* = .* custom-call\(",
                          text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode":
        assert "tpu_custom_call" not in text and temp <= 64_000_000
        return
    assert len(re.findall(r"%tile_attention\S* = .* custom-call\(",
                          text)) == 9
    assert text.count("tpu_custom_call") == 9 and temp <= 420_000_000


@pytest.mark.parametrize("program", ["decode", "tile"])
def test_mixtrals_step_runs_its_experts_through_the_grouped_kernel(
        one_chip, no_compile_cache, monkeypatch, program):
    """The step programs of `mixtral-8x7b.batch-longprompt` (4 scanned
    layers, 8 experts of 4,096 x 14,336, a 256-row tile with the 16 decode
    rows behind it). The TILE program's expert layer, whose capacity is
    the group's 272 rows, goes through the grouped kernel of
    ops/grouped_matmul.py (one call in the scan's body), which takes the
    group's rows `[272, 4096]` and hands back their result: the sorted
    rows and their results (eleven spans of two tiles of 128) never pass
    through HBM. The layers' experts reach it WHOLE, `[32, 4096, 14336]`,
    as bitcasts of the program's own parameters: no op's output is an
    expert's, a layer's or the stack's weights (under the scan a layer's
    weights are a slice of the parameters, and handed that slice the
    custom call had XLA copy it out, 2.82 GB a layer, temporaries 2.84 GB:
    read off this compile, PR 55; the dense form's einsums fuse the
    slice), no fusion makes the hidden of the dense dispatch
    `[8, 272, 14336]`, and the temporaries are 2.6 MB (the dense form's
    pair of `[8, 1, 272, 14336]` and their kin made them 79.7 MB). The
    DECODE program's groups are one row each: the dense dispatch, no
    grouped kernel. The decode rows keep the pool kernel in both."""
    import re
    eng, compiled = _step_program("mixtral-8x7b", program, one_chip,
                                  monkeypatch)
    text = compiled.as_text()
    calls = re.findall(r"%grouped_swiglu\S* = (\S+) custom-call\(([^)]*)\)",
                       text)
    assert len(re.findall(r"%pool_decode_attention\S* = .* custom-call\(",
                          text)) == 1
    made = set(re.findall(
        r"= bf16\[(?:\d+,)*(?:4096,14336|14336,4096)\]\S* ([\w-]+)\(", text))
    if program == "decode":
        assert not calls and text.count("tpu_custom_call") == 1
        assert made <= {"parameter", "get-tuple-element", "dynamic-slice",
                        "bitcast", "fusion"}
        return
    assert len(calls) == 1 and text.count("tpu_custom_call") == 2
    assert calls[0][0].startswith("bf16[272,4096]")
    assert made == {"parameter", "get-tuple-element", "bitcast"}, made
    views = re.findall(
        r"= bf16\[32,(?:4096,14336|14336,4096)\]\S* (\w+)\(", text)
    assert views == ["bitcast"] * 3, views
    assert not re.findall(r"\[8,(?:1,)?272,(?:14336|4096)\]", text)
    assert not re.findall(r"= bf16\[2816,4096\]", text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= 4_000_000
    # the weights, the pools and the scratch, as the parent's (12.709 GB)
    assert 12_709_000_000 < memory.argument_size_in_bytes < 12_710_000_000


def test_flash_by_name_never_returns_the_reference():
    """A length the kernel cannot tile raises; it is "auto" that chooses
    by platform and shape (here, on the CPU: the reference)."""
    from ray_tpu.ops.dispatch import attention
    x = jnp.zeros((1, 100, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="cannot tile"):
        attention(x, x, x, impl="flash")
    assert attention(x, x, x, impl="auto").shape == x.shape


@pytest.mark.parametrize("program", ["decode", "tile"])
def test_a_latent_kind_beside_states_reads_each_pool_where_it_lies(
        one_chip, no_compile_cache, monkeypatch, program):
    """The step programs of `ling-3.0-flash-vl.longctx-wide` (13 unrolled
    layers: 11 "kda" layers with a float32 state of 32 x 128 x 128 and a
    convolution's tail a slot, 2 latent layers; 32 slots of 6,144
    positions): the latent pool lies over the TWO latent layers alone and
    both latent kernels take them, counting that kind's layers (the decode
    rows' once a latent layer in either program, the tile's once a latent
    layer, which is what the engine's counters say: 2 of 2); neither the
    latent pool nor the states' or the tails' pool is copied by any op, no
    layer of a pool is sliced out, and a layer's new state is written into
    the running pool in place (eleven fusions whose result is the pool). The
    tile program's temporaries stay under 0.7 GB (0.614 at slots of 10,240,
    read off this compile, PR 58: the scans' float32 arrays of a 1,024-row tile), the
    decode program's under 50 MB."""
    import re
    eng, compiled = _step_program("ling-3.0-flash-vl", program, one_chip,
                                  monkeypatch)
    text = compiled.as_text()
    assert eng._slots.shapes == {
        "lat": (2, 32, 576, 6144), "s": (11, 32, 32, 128, 128),
        "c": (11, 32, 3, 12288)}
    assert eng._tile_layers == {1024: (2, 2)}
    for pool in (r"bf16\[2,32,576,6144\]", r"f32\[11,32,32,128,128\]",
                 r"f32\[11,32,3,12288\]"):
        assert not re.findall(rf"= {pool}\S* (?:copy|transpose)\(", text)
    assert not re.findall(r"= bf16\[32,576,6144\]", text)
    assert len(re.findall(r"= f32\[11,32,32,128,128\]\S* fusion\(",
                          text)) == 11
    rows = re.findall(r"%latent_pool_decode_attention\S* = .* custom-call\(",
                      text)
    tiles = re.findall(r"%latent_tile_attention\S* = .* custom-call\(", text)
    assert not re.findall(r"mla_row/\S*while", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode":
        assert len(rows) == 2 and not tiles and temp <= 50_000_000
        return
    assert len(rows) == 2 and len(tiles) == 2 and temp <= 700_000_000
