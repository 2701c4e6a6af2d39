"""The KV cache's storage (ray_tpu/inference/kv_cache.py) and its layout
(models/transformer.py kv_cache_shape / kv_cache_sharding): the block
store's contract in both formats, without an engine where none is
needed. Pure JAX on the CPU; no runtime is started."""

import numpy as np
import pytest

FORMATS = ("none", "int8")
CHUNK, ROW_LEN, N_ROWS = 4, 16, 2            # 8 blocks of 4 positions


@pytest.fixture(scope="module")
def jax_cpu():
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    return jax


def _cfg(**kw):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig
    base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=128, max_seq_len=128, dtype=jnp.float32,
                param_dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


CONFIGS = {
    "dense": dict(),
    "moe": dict(n_experts=4, expert_top_k=2, capacity_factor=2.0),
    # one KV head: nothing for a tensor axis to divide
    "narrow": dict(d_model=32, n_layers=1, n_heads=2, n_kv_heads=1, d_ff=64),
}


def _store(fmt, mcfg=None):
    import jax.numpy as jnp

    from ray_tpu.inference.kv_cache import BlockStore
    return BlockStore(mcfg or _cfg(), N_ROWS, ROW_LEN, CHUNK, jnp.float32,
                      fmt)


def _scratch(seed, mcfg=None, length=ROW_LEN + 8):
    """A (k, v) scratch of random values, rows of very different sizes
    (a scale per (position, head) has something to adapt to)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import kv_cache_shape
    shape = kv_cache_shape(mcfg or _cfg(), 1, length)
    rng = np.random.default_rng(seed)
    mag = 10.0 ** rng.integers(-3, 3, shape[:-1] + (1,))
    return tuple(jnp.asarray((rng.standard_normal(shape) * mag)
                             .astype(np.float32)) for _ in range(2))


def _stored(x):
    """What a block holds of computed values x, read back, by format:
    the values, or dequantize(quantize(x)) (kv_quant.py's arithmetic as
    a program computes it: a numpy mirror differs in a scale's last bit
    here and there)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference.kv_quant import dequantize_kv, quantize_kv
    q, s = jax.jit(quantize_kv)(jnp.asarray(x))
    back = jax.jit(dequantize_kv, static_argnums=2)(q, s, jnp.float32)
    return {"none": np.asarray(x), "int8": np.asarray(back)}


def _block_of(store, block):
    """A block's arrays as they lie in the store, on the host."""
    row, boff = divmod(block, store.blocks_per_row)
    return tuple(np.asarray(a[:, row:row + 1,
                              boff * store.chunk:(boff + 1) * store.chunk])
                 for a in store.arrays)


# (block, scratch offset saved from, scratch offset loaded to): both
# rows, first and last block of a row, offsets on and off block bounds
MOVES = ((0, 0, 4), (3, 8, 0), (4, 5, 12), (7, 20, 16), (5, 12, 12))


@pytest.mark.parametrize("fmt", FORMATS)
def test_save_then_load_returns_the_span(jax_cpu, fmt):
    """fp: the bits that were saved. int8: dequantize(quantize(x)), the
    numbers a miss attends after write-through and a hit restores."""
    store = _store(fmt)
    src = _scratch(0)
    for block, s_off, d_off in MOVES:
        store.save(src, block, s_off)
        # donated by load: a new zeroed scratch a move
        dst = tuple(np.zeros_like(np.asarray(a)) for a in src)
        out = store.load(tuple(map(jax_cpu.numpy.asarray, dst)), block,
                         d_off)
        for got, x in zip(out, src):
            got = np.array(got)
            want = _stored(x[:, :, s_off:s_off + CHUNK])[fmt]
            np.testing.assert_array_equal(
                got[:, :, d_off:d_off + CHUNK], want)
            got[:, :, d_off:d_off + CHUNK] = 0
            assert not got.any()             # and nothing beside it
    # an earlier block is still what was saved there
    want = _stored(src[0][:, :, 0:CHUNK])[fmt]
    got = store.load(_scratch(1), 0, 0)[0]
    np.testing.assert_array_equal(np.asarray(got)[:, :, :CHUNK], want)


@pytest.mark.parametrize("importer", FORMATS)
@pytest.mark.parametrize("exporter", FORMATS)
def test_export_then_import_across_formats(jax_cpu, exporter, importer):
    """Same format: the importer's block is the exporter's, bit for bit.
    fp into int8: what a local save of the same values gives. int8 into
    fp: reported as not exact (serve/disagg.py refuses it), and lands
    the dequantized values."""
    from ray_tpu.inference.kv_cache import span_format
    src = _scratch(2)
    out, inn, local = _store(exporter), _store(importer), _store(importer)
    for block, s_off, _ in MOVES:
        out.save(src, block, s_off)
        local.save(src, block, s_off)
        span = out.export(block)
        assert span_format(span) == exporter
        assert all(isinstance(a, np.ndarray) for a in span)
        for a, b in zip(span, _block_of(out, block)):
            np.testing.assert_array_equal(a, b)
        exact = inn.imports_exactly(span)
        assert exact == ((exporter, importer) != ("int8", "none"))
        dst = (block + 3) % inn.n_blocks     # lands where the trie says
        inn.import_span(span, dst)
        if exact:
            for a, b in zip(_block_of(inn, dst), _block_of(local, block)):
                np.testing.assert_array_equal(a, b)
        else:
            for a, x in zip(_block_of(inn, dst), src):
                np.testing.assert_array_equal(
                    a, _stored(x[:, :, s_off:s_off + CHUNK])["int8"])


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("program", ["save", "load", "export", "import"])
def test_each_program_compiles_once(jax_cpu, fmt, program):
    """Fixed span shape + traced block address and offsets: several
    blocks, rows and offsets are one executable."""
    store = _store(fmt)
    scratch = _scratch(3)
    for block, s_off, d_off in MOVES:
        if program == "save":
            store.save(scratch, block, s_off)
        elif program == "load":
            scratch = store.load(scratch, block, d_off)
        elif program == "export":
            store.export(block)
        else:
            store.import_span(_store(fmt).export(0), block)
    fn = getattr(store, f"_{program}_fn")
    assert fn._cache_size() == 1
    others = {"save", "load", "export", "import"} - {program}
    assert all(getattr(store, f"_{p}_fn")._cache_size() == 0
               for p in others)


@pytest.mark.parametrize("fmt", FORMATS)
def test_format_is_the_tuple_of_arrays(jax_cpu, fmt):
    import jax.numpy as jnp

    from ray_tpu.inference.kv_cache import format_stats, writes_through
    from ray_tpu.models.transformer import kv_cache_shape
    mcfg = _cfg()
    store = _store(fmt, mcfg)
    shape = kv_cache_shape(mcfg, N_ROWS, ROW_LEN)
    assert store.n_blocks == N_ROWS * ROW_LEN // CHUNK
    if fmt == "none":
        assert [(a.shape, a.dtype) for a in store.arrays] == \
            [(shape, jnp.float32)] * 2
        assert not writes_through(fmt)
        assert format_stats(fmt, mcfg.head_dim, 4) == {}
    else:
        assert [(a.shape, a.dtype) for a in store.arrays] == \
            [(shape, jnp.int8)] * 2 + [(shape[:-1], jnp.float32)] * 2
        assert writes_through(fmt)
        st = format_stats(fmt, mcfg.head_dim, 4)
        assert st["kv_quant"] == "int8" and st["kv_quant_slot_gain"] > \
            st["kv_quant_slot_gain_vs_fp16"] > 1.0
    with pytest.raises(ValueError):
        _store("fp8")


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_layout_is_said_once(jax_cpu, kind):
    """kv_cache_shape is init_cache's shape and the shape of every array
    a built engine keeps, whatever the model's widths."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference import EngineConfig, InferenceEngine
    from ray_tpu.models.transformer import (TransformerLM, init_cache,
                                            kv_cache_shape)
    mcfg = _cfg(**CONFIGS[kind])
    assert kv_cache_shape(mcfg, 3, 24) == (
        mcfg.n_layers, 3, 24, mcfg.n_kv_heads, mcfg.head_dim)
    cache = init_cache(mcfg, 3, 24)
    assert cache["k"].shape == cache["v"].shape == \
        kv_cache_shape(mcfg, 3, 24)
    model = TransformerLM(mcfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(
        model, params,
        EngineConfig(n_slots=2, max_len=32, prefill_chunk=4,
                     prefill_budget=8, prefix_cache_slots=1))
    pool = eng._slots
    assert pool.k.shape == pool.v.shape == kv_cache_shape(mcfg, 2, 32)
    assert pool.shapes == dict.fromkeys("kv", kv_cache_shape(mcfg, 2, 32))
    assert [a.shape for a in pool.new_scratch()] == \
        [kv_cache_shape(mcfg, 1, 32 + 8)] * 2
    assert [a.shape for a in eng._blocks.arrays] == \
        [kv_cache_shape(mcfg, 1, 32)] * 2
    assert eng.prefix_cache.n_blocks == eng._blocks.n_blocks == 8


def test_sharding_is_pruned_against_each_pools_own_shape(jax_cpu):
    """Batch over the data axes, KV heads over `tensor`; an axis the
    shape does not divide is left whole: a narrow model's single KV head
    on a 2-way tensor axis, where two heads split."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.inference.kv_cache import SlotPool
    from ray_tpu.models.transformer import (kv_cache_shape,
                                            kv_cache_sharding)
    from ray_tpu.parallel import MeshConfig, make_mesh
    devices = jax_cpu.devices()
    if len(devices) < 4:
        pytest.skip("needs four devices")
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, seq=1, tensor=2),
                     devices=devices[:4])
    target, narrow = _cfg(), _cfg(**CONFIGS["narrow"])
    sh = kv_cache_sharding(kv_cache_shape(target, 2, 16), mesh)
    assert sh.spec[3] == "tensor" and sh.spec[1] is not None
    # the form a program hands a donated pool back in: no trailing None
    assert sh.spec[0] is sh.spec[2] is None and len(sh.spec) == 4
    assert len(kv_cache_sharding(kv_cache_shape(narrow, 2, 16),
                                 mesh).spec) == 2
    # three slots do not divide the 2-way data axis
    assert kv_cache_sharding(kv_cache_shape(target, 3, 16), mesh).spec[1] \
        is None
    pool = SlotPool(narrow, 2, 16, 16, 24, jax_cpu.numpy.float32, mesh)
    sharding = kv_cache_sharding(pool.shapes["k"], mesh)
    assert pool.k.sharding == sharding
    assert pool.new_scratch()[0].sharding.spec == P()
    sk, sv = pool.new_scratch()
    pool.insert((sk + 1.0, sv + 2.0), 1)
    assert pool.k.sharding == sharding          # still where it was
    assert float(pool.k[0, 1, 3, 0, 0]) == 1.0 and \
        float(pool.v[0, 1, 15, 0, 0]) == 2.0 and not np.asarray(
            pool.k[:, 0]).any()
