"""Locality-aware streaming split dealing (reference: OutputSplitter
locality_hints, output_splitter.py — bundles deal to the consumer on the
block's node within a bounded row-imbalance slack)."""

import numpy as np

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.data.split import _QUEUE_CAP, _SplitCoordinator


RAY_START = dict(num_cpus=4, object_store_memory=128 * 1024 * 1024)


def _drain(coord, idx):
    rows = []
    while True:
        b = coord.next(idx)
        if b is None:
            return rows
        if b[0] == "__wait__":
            continue
        rows.append(ray_tpu.get(b[0]))


def test_locality_dealing_prefers_hinted_consumer(ray_start, monkeypatch):
    """With locations stubbed to alternate between two nodes, each
    consumer receives (almost) exactly its node's blocks."""
    ds = rd.range(160, parallelism=8).map_batches(lambda b: b)
    locs = {}

    def fake_locate(self, ref):
        # derive a stable fake location from the ref identity
        return locs.setdefault(ref.id, ["nodeA", "nodeB"][len(locs) % 2])

    monkeypatch.setattr(_SplitCoordinator, "_locate", fake_locate)
    coord = _SplitCoordinator(ds._stages, 2, False,
                              locality_hints=["nodeA", "nodeB"])
    got0 = _drain(coord, 0)
    got1 = _drain(coord, 1)
    assert len(got0) + len(got1) == 8
    hits, total = coord.locality_stats()
    assert total == 8 and hits == 8, (hits, total)
    # alternating fake locations -> exact 4/4 block, 80/80 row split
    assert len(got0) == 4 and len(got1) == 4
    assert sum(b.num_rows for b in got0) == 80
    assert sum(b.num_rows for b in got1) == 80


def test_locality_slack_caps_imbalance(ray_start, monkeypatch):
    """All blocks 'live' on node A: locality must yield to row balance
    once consumer 0 runs ahead by the slack — consumer 1 still gets a
    substantial share instead of starving."""
    ds = rd.range(400, parallelism=16).map_batches(lambda b: b)
    monkeypatch.setattr(_SplitCoordinator, "_locate",
                        lambda self, ref: "nodeA")
    coord = _SplitCoordinator(ds._stages, 2, False,
                              locality_hints=["nodeA", "nodeB"])
    got0 = _drain(coord, 0)
    got1 = _drain(coord, 1)
    rows0 = sum(b.num_rows for b in got0)
    rows1 = sum(b.num_rows for b in got1)
    assert rows0 + rows1 == 400
    assert rows1 > 0, "remote consumer starved"
    # slack = 4 bundles of 25 rows: consumer 0 may lead by <= ~125 rows
    assert rows0 - rows1 <= 4 * 25 + 25, (rows0, rows1)


def test_streaming_split_e2e_with_hints(ray_start):
    """Public API: hints flow through, stream completes, rows conserved
    (single node: every hint matches, pure smoke for the real _locate)."""
    me = ray_tpu._get_worker().core.node_id
    ds = rd.range(100, parallelism=4)
    shards = ds.streaming_split(2, locality_hints=[me, me])
    total = 0
    for sh in shards:
        for batch in sh.iter_batches(batch_size=None):
            total += len(batch["id"])
    assert total == 100