"""Engine: bytes of the slot pools the engine holds (K, V and, for a model
with an indexer, its keys), from InferenceEngine.stats() at the counters'
window's end, in GB. None where the program has no such counter."""


def read(run):
    c = (run.get("counters") or {}).get("t1") or {}
    return c["kv_pool_bytes"] / 1e9 if "kv_pool_bytes" in c else None
