"""Ray-Data-equivalent tests: lazy plans, streaming execution, transforms,
iteration incl. the jax device-feed path (reference:
python/ray/data/tests/test_map.py, test_iterator.py shapes)."""

import numpy as np

import ray_tpu
from ray_tpu import data as rd


RAY_START = dict(num_cpus=4, object_store_memory=256 * 1024 * 1024)


def test_range_count(ray_start):
    ds = rd.range(1000, parallelism=4)
    assert ds.count() == 1000
    assert ds.num_blocks() == 4


def test_map_batches(ray_start):
    ds = rd.range(100, parallelism=4).map_batches(
        lambda b: {"id": b["id"] * 2})
    got = sorted(r["id"] for r in ds.take_all())
    assert got == [i * 2 for i in range(100)]


def test_map_filter_flatmap(ray_start):
    ds = rd.range(20, parallelism=2) \
        .map(lambda r: {"v": r["id"] + 1}) \
        .filter(lambda r: r["v"] % 2 == 0) \
        .flat_map(lambda r: [{"v": r["v"]}, {"v": -r["v"]}])
    vals = sorted(r["v"] for r in ds.take_all())
    evens = [i + 1 for i in range(20) if (i + 1) % 2 == 0]
    assert vals == sorted(evens + [-v for v in evens])


def test_from_items_and_limit(ray_start):
    ds = rd.from_items([{"x": i} for i in range(50)], parallelism=5)
    assert ds.limit(7).count() == 7
    assert len(ds.take(3)) == 3


def test_repartition_and_shuffle(ray_start):
    ds = rd.range(100, parallelism=2).repartition(10)
    assert ds.num_blocks() == 10
    assert ds.count() == 100
    shuffled = rd.range(100, parallelism=4).random_shuffle(seed=0)
    vals = [r["id"] for r in shuffled.take_all()]
    assert sorted(vals) == list(range(100))
    assert vals != list(range(100))


def test_sort(ray_start):
    ds = rd.from_items([{"k": i % 7, "v": i} for i in range(30)])
    out = [r["k"] for r in ds.sort("k").take_all()]
    assert out == sorted(out)


def test_iter_batches_exact_sizes(ray_start):
    ds = rd.range(100, parallelism=7)
    batches = list(ds.iter_batches(batch_size=32, drop_last=False))
    sizes = [len(b["id"]) for b in batches]
    assert sizes == [32, 32, 32, 4]
    all_ids = np.concatenate([b["id"] for b in batches])
    assert sorted(all_ids.tolist()) == list(range(100))


def test_split(ray_start):
    shards = rd.range(90, parallelism=6).split(3)
    counts = [s.count() for s in shards]
    assert sum(counts) == 90
    assert all(c > 0 for c in counts)


def test_write_read_parquet(ray_start, tmp_path):
    ds = rd.range(64, parallelism=4).map_batches(
        lambda b: {"id": b["id"], "sq": b["id"] ** 2})
    ds.write_parquet(str(tmp_path / "pq"))
    back = rd.read_parquet(str(tmp_path / "pq"))
    rows = back.take_all()
    assert len(rows) == 64
    assert all(r["sq"] == r["id"] ** 2 for r in rows)


def test_iter_jax_batches(ray_start):
    import jax
    ds = rd.range(64, parallelism=4)
    batches = list(ds.iter_jax_batches(batch_size=16))
    assert len(batches) == 4
    assert all(isinstance(b["id"], jax.Array) for b in batches)
    total = sum(int(b["id"].sum()) for b in batches)
    assert total == sum(range(64))


def test_iter_jax_batches_sharded(ray_start):
    import jax
    from ray_tpu.parallel import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1, fsdp=8, seq=1, tensor=1))
    ds = rd.range(64, parallelism=4)
    for b in ds.iter_jax_batches(batch_size=16, mesh=mesh):
        assert b["id"].sharding.num_devices == 8


def test_groupby_aggregates(ray_start):
    import ray_tpu.data as rd
    ds = rd.from_items([{"k": i % 3, "v": float(i)} for i in range(30)])
    out = {r["k"]: r["sum(v)"] for r in ds.groupby("k").sum("v").take_all()}
    expect = {}
    for i in range(30):
        expect[i % 3] = expect.get(i % 3, 0.0) + float(i)
    assert out == expect
    counts = {r["k"]: r["count()"]
              for r in ds.groupby("k").count().take_all()}
    assert counts == {0: 10, 1: 10, 2: 10}
    means = {r["k"]: r["mean(v)"]
             for r in ds.groupby("k").mean("v").take_all()}
    assert abs(means[0] - expect[0] / 10) < 1e-9


def test_map_groups(ray_start):
    import ray_tpu.data as rd
    ds = rd.from_items([{"k": i % 2, "v": float(i)} for i in range(10)])

    def top1(df):
        return df.nlargest(1, "v")

    rows = ds.groupby("k").map_groups(top1).take_all()
    assert sorted(r["v"] for r in rows) == [8.0, 9.0]


def test_column_ops_and_global_aggs(ray_start):
    import ray_tpu.data as rd
    ds = rd.from_items([{"a": i, "b": 2 * i} for i in range(10)])
    ds2 = ds.add_column("c", lambda df: df["a"] + df["b"])
    row = ds2.sort("a").take(1)[0]
    assert row["c"] == 0
    assert ds2.max("c") == 27.0
    assert ds2.sum("a") == 45.0
    assert abs(ds2.mean("b") - 9.0) < 1e-9
    ds3 = ds2.drop_columns(["b"]).rename_columns({"c": "total"})
    assert sorted(ds3.take(1)[0].keys()) == ["a", "total"]
    assert ds.unique("a") == list(range(10))


def test_random_split_and_zip(ray_start):
    import ray_tpu.data as rd
    ds = rd.range(20)
    a, b = ds.random_split([0.5, 0.5], seed=0)
    assert a.count() + b.count() == 20
    z = rd.range(5).zip(rd.from_items([{"y": i * 10} for i in range(5)]))
    rows = z.sort("id").take_all()
    assert rows[2]["y"] == 20 or "y" in rows[2]


def test_preprocessors(ray_start):
    import numpy as np

    import ray_tpu.data as rd
    from ray_tpu.data.preprocessors import (Chain, LabelEncoder,
                                            MinMaxScaler, OneHotEncoder,
                                            StandardScaler)
    ds = rd.from_items([{"x": float(i), "cat": ["a", "b"][i % 2],
                         "label": ["lo", "hi"][i // 5]} for i in range(10)])
    scaled = StandardScaler(["x"]).fit_transform(ds)
    xs = np.array([r["x"] for r in scaled.take_all()])
    assert abs(xs.mean()) < 1e-9 and abs(xs.std() - 1.0) < 1e-6

    mm = MinMaxScaler(["x"]).fit_transform(ds)
    xs = np.array([r["x"] for r in mm.take_all()])
    assert xs.min() == 0.0 and xs.max() == 1.0

    enc = LabelEncoder("label").fit_transform(ds)
    labels = {r["label"] for r in enc.take_all()}
    assert labels == {0, 1}

    oh = OneHotEncoder(["cat"]).fit_transform(ds)
    r0 = oh.sort("x").take(1)[0]
    assert r0["cat_a"] == 1 and r0["cat_b"] == 0

    chain = Chain(StandardScaler(["x"]), LabelEncoder("label"))
    out = chain.fit(ds).transform(ds).take_all()
    assert {r["label"] for r in out} == {0, 1}


def test_write_json(ray_start, tmp_path):
    import json
    import os

    import ray_tpu.data as rd
    p = str(tmp_path / "out")
    rd.range(7).write_json(p)
    rows = []
    for f in sorted(os.listdir(p)):
        with open(os.path.join(p, f)) as fh:
            rows += [json.loads(l) for l in fh]
    assert sorted(r["id"] for r in rows) == list(range(7))
