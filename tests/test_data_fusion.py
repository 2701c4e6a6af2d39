"""Operator fusion + per-op stats (reference:
python/ray/data/_internal/logical/rules/operator_fusion.py and
_internal/stats.py — fused map chains pay one task per block; ds.stats()
reports tasks/rows/bytes/wall per operator)."""

import ray_tpu
import ray_tpu.data as rd
from ray_tpu.data import execution as exe


RAY_START = dict(num_cpus=2, object_store_memory=128 * 1024 * 1024)


def test_fusion_rule_plan_shape():
    a = exe.MapStage("map", lambda r: r)
    b = exe.MapStage("filter", lambda r: True)
    c = exe.AllToAllStage("repartition", num_blocks=2)
    d = exe.MapStage("map_batches", lambda x: x)
    plan = exe.optimize_plan([exe.InputStage([]), a, b, c, d])
    kinds = [type(s).__name__ for s in plan]
    assert kinds == ["InputStage", "MapStage", "AllToAllStage", "MapStage"]
    assert [k for k, *_ in plan[1].ops] == ["map", "filter"]
    assert plan[1].name == "Map(map->filter)"


def test_actor_pool_is_fusion_barrier():
    a = exe.MapStage("map", lambda r: r)
    pool = exe.ActorPoolMapStage.__new__(exe.ActorPoolMapStage)
    b = exe.MapStage("map", lambda r: r)
    plan = exe.optimize_plan([a, pool, b])
    assert len(plan) == 3


def test_fused_two_maps_half_the_tasks(ray_start):
    n_blocks = 4
    ds = rd.range(400, parallelism=n_blocks) \
        .map(lambda r: {"id": r["id"], "x": r["id"] * 2}) \
        .filter(lambda r: r["x"] % 4 == 0)
    rows = ds.take_all()
    assert len(rows) == 200
    assert all(r["x"] % 4 == 0 and r["x"] == r["id"] * 2 for r in rows)
    stats = ds.stats()
    # one Read op + ONE fused map op, each n_blocks tasks: the unfused
    # plan would show two map operators = 2x the object-store round trips
    lines = [ln for ln in stats.splitlines() if "Map(" in ln]
    assert len(lines) == 1, stats
    assert "Map(map->filter)" in lines[0], stats
    assert f"{n_blocks} tasks" in lines[0], stats


def test_stats_reports_rows_and_bytes(ray_start):
    ds = rd.range(100, parallelism=2).map_batches(lambda b: b)
    rows = ds.take_all()
    assert len(rows) == 100
    s = ds.stats()
    assert "Read" in s and "100 rows" in s and "Total:" in s, s


def test_limit_pushdown_past_map(ray_start):
    """range(10k).map(f).limit(50): the limit moves ahead of the map, so
    only ~1 block's rows are mapped instead of all 10k (reference:
    logical/rules/limit_pushdown.py)."""
    plan = exe.optimize_plan([
        exe.InputStage([]),
        exe.MapStage("map", lambda r: r),
        exe.LimitStage(50),
    ])
    kinds = [type(s).__name__ for s in plan]
    assert kinds == ["InputStage", "LimitStage", "MapStage", "LimitStage"]
    # NOT pushed past cardinality-changing stages
    plan2 = exe.optimize_plan([
        exe.MapStage("filter", lambda r: True), exe.LimitStage(5)])
    assert [type(s).__name__ for s in plan2] == ["MapStage", "LimitStage"]
    # end-to-end correctness
    ds = rd.range(10_000, parallelism=8) \
        .map(lambda r: {"v": r["id"] * 2}).limit(50)
    rows = ds.take_all()
    assert [r["v"] for r in rows] == [i * 2 for i in range(50)]
    stats = ds.stats()
    map_line = next(ln for ln in stats.splitlines() if "Map(" in ln)
    # the pushed-down limit cuts BEFORE the map: 50 rows mapped, not 10k
    assert " 50 rows" in map_line, stats


def test_fused_semantics_match_unfused(ray_start):
    base = rd.range(60, parallelism=3)
    fused = base.map(lambda r: {"v": r["id"] + 1}) \
        .flat_map(lambda r: [r, r]) \
        .filter(lambda r: r["v"] % 2 == 0)
    got = sorted(r["v"] for r in fused.take_all())
    expect = sorted(v for i in range(60) for v in [i + 1, i + 1]
                    if v % 2 == 0)
    assert got == expect
