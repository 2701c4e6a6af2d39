"""Push-based streaming shuffle (ray_tpu/data/shuffle.py).

Two tiers of coverage:

1. A hermetic fake-runtime harness (eager in-process task execution with
   pluggable completion ORDER) drives the real driver-side streaming
   logic — windowed map launch, contiguous merge-run folding, reduce
   ordering, the peak-live gauges, and seed determinism independent of
   task completion timing. These run everywhere, no cluster needed.

2. Cluster end-to-end tests (spill-backed overflow, lineage recovery of
   a killed reduce output, cross-run determinism) — gated on the
   runtime's Python floor, slow tier where multi-node.
"""


import numpy as np
import pytest

import ray_tpu
from ray_tpu.data import block as block_lib
from ray_tpu.data import exchange
from ray_tpu.data import shuffle as shuffle_lib


# --------------------------------------------------------- fake runtime
class _Ref:
    __slots__ = ("val",)

    def __init__(self, val):
        self.val = val


def _unwrap(x):
    return x.val if isinstance(x, _Ref) else x


class _FakeTask:
    def __init__(self, fn, opts):
        self.fn, self.opts = fn, opts

    def options(self, **kw):
        return _FakeTask(self.fn, {**self.opts, **kw})

    def remote(self, *args, **kwargs):
        out = self.fn(*[_unwrap(a) for a in args],
                      **{k: _unwrap(v) for k, v in kwargs.items()})
        n = self.opts.get("num_returns", 1)
        if n == 1:
            return _Ref(out)
        out = list(out)
        assert len(out) == n, (len(out), n)
        return [_Ref(v) for v in out]


def _fake_remote(fn=None, **opts):
    if fn is None:
        return lambda f: _FakeTask(f, opts)
    return _FakeTask(fn, opts)


def _fake_get(refs, **_kw):
    if isinstance(refs, list):
        return [_unwrap(r) for r in refs]
    return _unwrap(refs)


def _make_fake_wait(order: str):
    """Completion-order knob: 'fifo' hands back the oldest in-flight
    task first, 'lifo' the newest — determinism must survive both."""

    def _wait(refs, num_returns=1, timeout=None):
        refs = list(refs)
        if order == "lifo":
            ready = refs[-num_returns:]
        else:
            ready = refs[:num_returns]
        rest = [r for r in refs if r not in ready]
        return ready, rest

    return _wait


@pytest.fixture(params=["fifo", "lifo"])
def fake_runtime(request, monkeypatch):
    monkeypatch.setattr(ray_tpu, "remote", _fake_remote)
    monkeypatch.setattr(ray_tpu, "get", _fake_get)
    monkeypatch.setattr(ray_tpu, "wait", _make_fake_wait(request.param))
    monkeypatch.setattr(ray_tpu, "put", lambda v: _Ref(v))
    monkeypatch.setattr(ray_tpu, "is_initialized", lambda: False)
    return request.param


def _bundles(nblocks, rows_per=50, key_mod=None):
    out = []
    for i in range(nblocks):
        ids = np.arange(i * rows_per, (i + 1) * rows_per)
        cols = {"id": ids}
        if key_mod:
            cols["k"] = ids % key_mod
        blk = block_lib.block_from_batch(cols)
        out.append((_Ref(blk), block_lib.block_metadata(blk)))
    return out


def _rows(stage, bundles, budget=None):
    out = []
    for ref, _meta in stage.execute(iter(bundles), budget):
        out.extend(block_lib.block_to_rows(_unwrap(ref)))
    return out


# ------------------------------------------------- fake-runtime coverage
def test_streaming_shuffle_permutation_deterministic(fake_runtime):
    """Same seed -> identical output ORDER, regardless of task
    completion order; output is an exact permutation of the input."""
    n_blocks, rows = 24, 50
    runs = []
    for _ in range(2):
        st = shuffle_lib.ShuffleStage("random_shuffle", seed=7)
        ids = [r["id"] for r in _rows(st, _bundles(n_blocks, rows))]
        assert not st.stats.fallback
        assert st.stats.map_tasks == n_blocks
        runs.append(ids)
    assert runs[0] == runs[1]
    assert sorted(runs[0]) == list(range(n_blocks * rows))
    assert runs[0] != sorted(runs[0])
    # a different seed permutes differently
    st2 = shuffle_lib.ShuffleStage("random_shuffle", seed=8)
    assert [r["id"] for r in _rows(st2, _bundles(n_blocks, rows))] != runs[0]


def test_peak_live_inputs_bounded(fake_runtime):
    """The memory-bound evidence: the stage never holds more than the
    in-flight window of input-block refs, no matter how many blocks
    stream through, and intermediate merges keep per-partition unmerged
    sub-block refs bounded too."""
    n_blocks = 64
    st = shuffle_lib.ShuffleStage("random_shuffle", seed=1)
    rows = _rows(st, _bundles(n_blocks, 20))
    assert len(rows) == n_blocks * 20
    g = st.stats
    assert g.input_blocks == n_blocks
    assert g.peak_live_inputs <= shuffle_lib.DEFAULT_MAX_MAPS
    assert g.peak_live_inputs < n_blocks
    assert g.merge_tasks > 0                 # runs actually folded
    total_subblocks = n_blocks * g.num_partitions
    assert g.peak_live_partials < total_subblocks
    # structural bound independent of dataset size: stuck window slots +
    # up to two partially-filled runs per partition
    assert g.peak_live_partials <= g.num_partitions * (
        shuffle_lib.DEFAULT_MAX_MAPS + 2 * shuffle_lib.DEFAULT_MERGE_FACTOR)
    assert shuffle_lib.last_shuffle_stats() is g


def test_streaming_repartition_exact_block_count(fake_runtime):
    st = shuffle_lib.ShuffleStage("repartition", num_blocks=6)
    bundles = _bundles(10, 37)
    out = list(st.execute(iter(bundles)))
    assert len(out) == 6                     # exact contract, empties kept
    rows = []
    sizes = []
    for ref, meta in out:
        blk = _unwrap(ref)
        sizes.append(blk.num_rows)
        rows.extend(block_lib.block_to_rows(blk))
    assert sorted(r["id"] for r in rows) == list(range(370))
    assert max(sizes) - min(sizes) <= 10     # round-robin balance


def test_streaming_sort_globally_ordered(fake_runtime):
    rng = np.random.default_rng(0)
    vals = rng.permutation(4000)
    bundles = []
    for chunk in np.array_split(vals, 16):
        blk = block_lib.block_from_batch({"v": chunk})
        bundles.append((_Ref(blk), block_lib.block_metadata(blk)))
    st = shuffle_lib.ShuffleStage("sort", key="v")
    got = [r["v"] for r in _rows(st, bundles)]
    assert got == list(range(4000))
    st_d = shuffle_lib.ShuffleStage("sort", key="v", descending=True)
    got_d = [r["v"] for r in _rows(st_d, bundles)]
    assert got_d == list(range(3999, -1, -1))


def test_streaming_groupby_sum(fake_runtime):
    st = shuffle_lib.ShuffleStage(
        "groupby_agg", key="k", aggs=[("id", "sum", "sum(id)")])
    rows = _rows(st, _bundles(12, 40, key_mod=5))
    assert len(rows) == 5
    got = {int(r["k"]): r["sum(id)"] for r in rows}
    n = 12 * 40
    for k in range(5):
        assert got[k] == sum(i for i in range(n) if i % 5 == k)


def test_unseeded_shuffle_still_permutes(fake_runtime):
    """seed=None must still permute (fresh per-execution entropy), not
    degenerate to map-index order within partitions."""
    st = shuffle_lib.ShuffleStage("random_shuffle", seed=None)
    ids_a = [r["id"] for r in _rows(st, _bundles(16, 40))]
    assert sorted(ids_a) == list(range(640))
    assert ids_a != sorted(ids_a)
    st_b = shuffle_lib.ShuffleStage("random_shuffle", seed=None)
    ids_b = [r["id"] for r in _rows(st_b, _bundles(16, 40))]
    assert ids_a != ids_b          # fresh entropy per execution


def test_tiny_input_falls_back_to_legacy(fake_runtime):
    st = shuffle_lib.ShuffleStage("random_shuffle", seed=3)
    rows = _rows(st, _bundles(2, 30))
    assert st.stats.fallback
    assert sorted(r["id"] for r in rows) == list(range(60))


def test_merge_factor_controls_fold_granularity(fake_runtime):
    st = shuffle_lib.ShuffleStage("random_shuffle", seed=5, merge_factor=4,
                                  num_partitions=4)
    rows = _rows(st, _bundles(32, 10))
    assert len(rows) == 320
    # 32 maps -> 8 complete runs of 4 per partition
    assert st.stats.merge_tasks == 4 * (32 // 4)


# ------------------------------------------------------ unit-level bits
def test_partition_round_robin_balance_and_empty():
    blk = block_lib.block_from_batch({"id": np.arange(10)})
    parts = exchange.partition_round_robin(blk, 3)
    assert [p.num_rows for p in parts] == [4, 3, 3]
    empty = block_lib.block_from_batch({"id": np.arange(0)})
    assert [p.num_rows for p in exchange.partition_round_robin(empty, 3)] \
        == [0, 0, 0]


def test_concat_blocks_preserves_schema_when_all_empty():
    blk = block_lib.block_from_batch({"a": np.arange(5), "b": np.arange(5)})
    empty = blk.slice(0, 0)
    out = block_lib.concat_blocks([empty, empty])
    assert out.num_rows == 0
    assert out.column_names == ["a", "b"]


def test_plurality_node_weighs_bytes(monkeypatch):
    locs = {"r1": "nodeA", "r2": "nodeB", "r3": "nodeB", "r4": None}
    monkeypatch.setattr(shuffle_lib, "object_node_ids",
                        lambda refs: [locs[r] for r in refs])
    # nodeA holds 100 bytes in one ref; nodeB holds 30 across two
    assert shuffle_lib.plurality_node(
        [("r1", 100), ("r2", 10), ("r3", 20), ("r4", 500)]) == "nodeA"
    assert shuffle_lib.plurality_node([("r4", 500)]) is None
    assert shuffle_lib.plurality_node([]) is None


def test_derived_seed_stability():
    assert shuffle_lib._derived_seed(None, 0, 3) is None
    a = shuffle_lib._derived_seed(7, 0, 3)
    assert a == shuffle_lib._derived_seed(7, 0, 3)
    assert a != shuffle_lib._derived_seed(7, 1, 3)
    assert a != shuffle_lib._derived_seed(7, 0, 4)


# --------------------------------------------------- cluster end-to-end
ROW_PAD = 8192            # bytes of payload per row


def _fat_dataset(total_bytes: int, parallelism: int = 16):
    import ray_tpu.data as rd
    n_rows = total_bytes // (ROW_PAD + 8)
    pad = "x" * ROW_PAD

    def fatten(batch):
        return {"id": batch["id"],
                "pad": np.array([pad] * len(batch["id"]), dtype=object)}

    return n_rows, rd.range(n_rows, parallelism=parallelism) \
        .map_batches(fatten)


@pytest.mark.slow
def test_shuffle_2x_store_budget_completes_via_spill():
    """Acceptance: random_shuffle on a dataset >= 2x the object-store
    budget completes, with the stage never holding all input blocks
    live (peak live-block gauge)."""
    store = 64 * 1024 * 1024
    ray_tpu.init(num_cpus=4, object_store_memory=store)
    try:
        n_rows, ds = _fat_dataset(2 * store + 16 * 1024 * 1024)
        total = 0
        checksum = 0
        for batch in ds.random_shuffle(seed=11).iter_batches(
                batch_size=4096, batch_format="numpy"):
            total += len(batch["id"])
            checksum += int(batch["id"].sum())
        assert total == n_rows
        assert checksum == n_rows * (n_rows - 1) // 2
        g = shuffle_lib.last_shuffle_stats()
        assert g is not None and not g.fallback
        assert g.peak_live_inputs < g.input_blocks
        assert g.peak_live_inputs <= shuffle_lib.DEFAULT_MAX_MAPS
    finally:
        ray_tpu.shutdown()


def test_shuffle_seed_deterministic_on_cluster():
    import ray_tpu.data as rd
    ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024 * 1024)
    try:
        runs = []
        for _ in range(2):
            ds = rd.range(20_000, parallelism=8).random_shuffle(seed=123)
            runs.append([r["id"] for b in ds.iter_batches(
                batch_size=5000, batch_format="numpy") for r in
                ({"id": int(v)} for v in b["id"])])
        assert runs[0] == runs[1]
        assert sorted(runs[0]) == list(range(20_000))
        assert runs[0] != sorted(runs[0])
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
def test_reduce_output_killed_mid_shuffle_recovers_via_lineage():
    """A shuffle output living only on a killed node is reconstructed
    through the map->merge->reduce lineage chain on fetch."""
    import ray_tpu.data as rd
    from ray_tpu.cluster_utils import Cluster
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 2,
                                "object_store_memory": 128 * 1024 * 1024})
    n2 = c.add_node(num_cpus=2, object_store_memory=128 * 1024 * 1024)
    ray_tpu.init(address=c.address)
    try:
        ds = rd.range(100_000, parallelism=8).random_shuffle(seed=5)
        refs = ds.get_internal_block_refs()
        assert refs
        import time as _t
        _t.sleep(0.5)
        c.remove_node(n2)
        _t.sleep(1.0)
        total = 0
        checksum = 0
        for ref in refs:
            blk = ray_tpu.get(ref, timeout=120)
            total += blk.num_rows
            checksum += sum(blk.column("id").to_pylist())
        assert total == 100_000
        assert checksum == 100_000 * 99_999 // 2
    finally:
        ray_tpu.shutdown()
        c.shutdown()
