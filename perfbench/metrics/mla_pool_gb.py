"""Engine: bytes of the latent pool the engine holds for its slots (one row
of latent + rope key a position a layer, whatever the heads), from
InferenceEngine.stats() at the counters' window's end, in GB, as counted
from the pool's shape (the TPU stores a row of 576 in five lane tiles:
PERF.md section 4); `kv_pool_gb` is the same number for a model whose only
cache it is. None where the program has no such counter."""


def read(run):
    c = (run.get("counters") or {}).get("t1") or {}
    return c["latent_pool_bytes"] / 1e9 if "latent_pool_bytes" in c else None
