"""Engine: bytes of the RINGS the engine holds for its slots (K and V of the
sliding-window layers, window + prefill budget positions a slot whatever
the slot's length), from InferenceEngine.stats() at the counters' window's
end, in GB; `kv_pool_gb` counts them too, beside the caches by position.
None where the program has no such counter."""


def read(run):
    c = (run.get("counters") or {}).get("t1") or {}
    return c["win_pool_bytes"] / 1e9 if "win_pool_bytes" in c else None
