"""Data adoption of core streaming generators (round-5): generator
map_batches UDFs fan one block into many without buffering the
expansion, and parquet reads stream per row group (reference:
map_transformer generator UDFs; parquet fragment reads)."""

import numpy as np

import ray_tpu
import ray_tpu.data as rd


RAY_START = dict(num_cpus=2, object_store_memory=128 * 1024 * 1024)


def test_generator_udf_streams_chunks(ray_start):
    """A map_batches UDF that yields K chunks per input block produces
    K output blocks, in order."""
    def expand(batch):
        n = len(batch["id"])
        for k in range(3):
            yield {"id": batch["id"] * 10 + k, "chunk": np.full(n, k)}

    ds = rd.range(40, parallelism=4).map_batches(expand)
    rows = ds.take_all()
    assert len(rows) == 120
    chunks = [r["chunk"] for r in rows]
    assert set(chunks) == {0, 1, 2}
    ids = sorted(r["id"] for r in rows if r["chunk"] == 1)
    assert ids == [i * 10 + 1 for i in range(40)]


def test_generator_udf_fuses_with_downstream_map(ray_start):
    """Fusion across a generator UDF: each streamed chunk flows through
    the fused downstream op inside the same task."""
    def expand(batch):
        yield {"v": batch["id"]}
        yield {"v": batch["id"] + 100}

    ds = (rd.range(10, parallelism=2)
          .map_batches(expand)
          .map(lambda r: {"v2": r["v"] * 2}))
    vals = sorted(r["v2"] for r in ds.take_all())
    expect = sorted([i * 2 for i in range(10)]
                    + [(i + 100) * 2 for i in range(10)])
    assert vals == expect


def test_parquet_row_groups_stream_as_blocks(ray_start, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    table = pa.table({"x": list(range(1000))})
    path = str(tmp_path / "rg.parquet")
    pq.write_table(table, path, row_group_size=100)    # 10 row groups
    ds = rd.read_parquet(path)
    assert sorted(r["x"] for r in ds.take_all()) == list(range(1000))
    # one block per row group (the stream fanned the file out)
    assert ds.num_blocks() == 10


def test_stats_cover_streamed_stages(ray_start):
    def expand(batch):
        yield {"a": batch["id"]}
        yield {"a": batch["id"]}

    ds = rd.range(20, parallelism=2).map_batches(expand)
    ds.take_all()
    # per-op stats still render for streamed stages
    assert "Map" in ds.stats()
