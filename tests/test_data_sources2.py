"""Projection pushdown + sql/webdataset sources (reference:
python/ray/data logical/rules projection pushdown,
_internal/datasource/sql_datasource.py, webdataset_datasource.py)."""

import os
import sqlite3
import tarfile

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.data import execution as exe


RAY_START = dict(num_cpus=4, object_store_memory=128 * 1024 * 1024)


@pytest.fixture()
def pq_file(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    t = pa.table({"a": np.arange(100, dtype=np.int64),
                  "b": np.arange(100, dtype=np.float64) * 2.0,
                  "payload": [b"x" * 1000] * 100})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, row_group_size=25)
    return path


def test_projection_pushdown_rebinds_read(pq_file):
    """The optimized plan's ReadStage must be rebound to the projected
    columns (plan-level check, no cluster needed)."""
    ds = rd.read_parquet(pq_file).select_columns(["a"])
    optimized = exe.optimize_plan(list(ds._stages))
    read = optimized[0]
    assert isinstance(read, exe.ReadStage)
    # rebound fns read only column "a": execute one locally and check
    blocks = list(read.read_fns[0]())
    assert blocks[0].column_names == ["a"]


def test_projection_pushdown_chained_selects(pq_file):
    """Chained selects: only the FIRST (widest) projection pushes into
    the read — pushing the narrower one would starve the earlier select
    of its columns (round-5 review finding)."""
    ds = rd.read_parquet(pq_file).select_columns(["a", "b"]) \
        .select_columns(["a"])
    optimized = exe.optimize_plan(list(ds._stages))
    blocks = list(optimized[0].read_fns[0]())
    assert set(blocks[0].column_names) == {"a", "b"}


def test_projection_chained_end_to_end(ray_start, pq_file):
    rows = rd.read_parquet(pq_file).select_columns(["a", "b"]) \
        .select_columns(["a"]).take(3)
    assert rows == [{"a": 0}, {"a": 1}, {"a": 2}]


def test_read_sql_sharded_with_order_by(ray_start, tmp_path):
    """Sharded read of a query with ORDER BY (round-5 review: WHERE
    splicing broke on any ORDER BY/GROUP BY/LIMIT suffix)."""
    db = str(tmp_path / "ob.db")
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE kv (k INTEGER, grp TEXT)")
    conn.executemany("INSERT INTO kv VALUES (?, ?)",
                     [(i, "ab"[i % 2]) for i in range(10)])
    conn.commit()
    conn.close()
    ds = rd.read_sql("SELECT k, grp FROM kv WHERE k >= 2 ORDER BY k",
                     lambda: sqlite3.connect(db),
                     shard_column="grp", shard_keys=["a", "b"])
    rows = ds.take_all()
    assert sorted(r["k"] for r in rows) == list(range(2, 10))


def test_projection_pushdown_through_limit(pq_file):
    ds = rd.read_parquet(pq_file).limit(10).select_columns(["b"])
    optimized = exe.optimize_plan(list(ds._stages))
    blocks = list(optimized[0].read_fns[0]())
    assert blocks[0].column_names == ["b"]


def test_projection_not_pushed_past_udf(pq_file):
    """An arbitrary map between read and project may need the dropped
    columns — the read must stay unpruned."""
    ds = rd.read_parquet(pq_file) \
        .map(lambda r: {**r, "c": r["b"] + 1}) \
        .select_columns(["c"])
    optimized = exe.optimize_plan(list(ds._stages))
    blocks = list(optimized[0].read_fns[0]())
    assert set(blocks[0].column_names) == {"a", "b", "payload"}


def test_projection_end_to_end(ray_start, pq_file):
    rows = rd.read_parquet(pq_file).select_columns(["a"]).take(5)
    assert rows == [{"a": i} for i in range(5)]
    # explicit columns= arg works without a projection stage
    rows = rd.read_parquet(pq_file, columns=["b"]).take(2)
    assert rows == [{"b": 0.0}, {"b": 2.0}]


def test_read_sql_single_and_sharded(ray_start, tmp_path):
    db = str(tmp_path / "t.db")
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE kv (k INTEGER, grp TEXT, v REAL)")
    conn.executemany("INSERT INTO kv VALUES (?, ?, ?)",
                     [(i, "ab"[i % 2], float(i)) for i in range(20)])
    conn.commit()
    conn.close()

    ds = rd.read_sql("SELECT k, v FROM kv ORDER BY k",
                     lambda: sqlite3.connect(db))
    rows = ds.take_all()
    assert len(rows) == 20 and rows[0] == {"k": 0, "v": 0.0}

    sharded = rd.read_sql("SELECT k, grp FROM kv",
                          lambda: sqlite3.connect(db),
                          shard_column="grp", shard_keys=["a", "b"])
    assert sharded.num_blocks() == 2
    rows = sharded.take_all()
    assert len(rows) == 20
    assert {r["grp"] for r in rows} == {"a", "b"}


def test_read_webdataset(ray_start, tmp_path):
    import io
    import json as json_mod
    shard = str(tmp_path / "shard-000.tar")
    with tarfile.open(shard, "w") as tar:
        for i in range(3):
            for ext, payload in [
                    ("cls", str(i).encode()),
                    ("txt", f"sample {i}".encode()),
                    ("json", json_mod.dumps({"idx": i}).encode())]:
                data = io.BytesIO(payload)
                info = tarfile.TarInfo(f"sample{i:03d}.{ext}")
                info.size = len(payload)
                tar.addfile(info, data)
    ds = rd.read_webdataset(shard)
    rows = ds.take_all()
    assert len(rows) == 3
    r0 = next(r for r in rows if r["__key__"] == "sample000")
    assert r0["cls"] == 0 and r0["txt"] == "sample 0"
    assert r0["json"] == {"idx": 0}


# ------------------------------------------------- mongo / bigquery fakes
class _FakeMongoCollection:
    def __init__(self, docs):
        self._docs = docs

    def aggregate(self, pipeline):
        docs = self._docs
        for stage in pipeline:
            if "$match" in stage:
                m = stage["$match"]
                docs = [d for d in docs
                        if all(d.get(k) == v for k, v in m.items())]
            elif "$project" in stage:
                keep = [k for k, v in stage["$project"].items() if v]
                docs = [{k: d[k] for k in keep if k in d} for d in docs]
            else:
                raise ValueError(f"fake mongo: unsupported stage {stage}")
        return iter(docs)


class _FakeMongoClient:
    """pymongo surface: client[db][coll].aggregate(...)"""

    def __init__(self):
        self.closed = False

    def __getitem__(self, db):
        return {"events": _FakeMongoCollection(
            [{"_id": _FakeObjectId(i), "grp": "ab"[i % 2], "v": i}
             for i in range(10)])}

    def close(self):
        self.closed = True


class _FakeObjectId:
    """Non-arrow-native id type: read_mongo must stringify it."""

    def __init__(self, i):
        self.i = i

    def __str__(self):
        return f"oid-{self.i:04d}"


def test_read_mongo_single_and_sharded(ray_start):
    ds = rd.read_mongo("mongodb://unused", "db", "events",
                       client_factory=_FakeMongoClient)
    rows = ds.take_all()
    assert len(rows) == 10
    assert rows[0]["_id"].startswith("oid-")  # ObjectId stringified

    sharded = rd.read_mongo(
        "mongodb://unused", "db", "events",
        pipeline=[{"$project": {"grp": 1, "v": 1}}],
        shard_match=[{"grp": "a"}, {"grp": "b"}],
        client_factory=_FakeMongoClient)
    assert sharded.num_blocks() == 2
    rows = sharded.take_all()
    assert len(rows) == 10
    assert {r["grp"] for r in rows} == {"a", "b"}
    assert all("_id" not in r for r in rows)  # $project applied


class _FakeBqJob:
    def __init__(self, rows):
        self._rows = rows

    def result(self):
        return iter(self._rows)


class _FakeBqClient:
    """google-cloud-bigquery surface: client.query(sql).result()"""

    def query(self, sql):
        assert "FROM" in sql, sql
        if "`ds.t`" in sql:   # whole-table form built by read_bigquery
            return _FakeBqJob([{"x": i, "name": f"n{i}"} for i in range(5)])
        return _FakeBqJob([{"x": 1}])


def test_read_bigquery_query_and_table(ray_start):
    ds = rd.read_bigquery("SELECT x FROM t", client_factory=_FakeBqClient)
    assert ds.take_all() == [{"x": 1}]

    ds2 = rd.read_bigquery(dataset="ds.t", client_factory=_FakeBqClient)
    rows = ds2.take_all()
    assert len(rows) == 5 and rows[0] == {"x": 0, "name": "n0"}

    with pytest.raises(ValueError, match="query.*or.*dataset"):
        rd.read_bigquery()
