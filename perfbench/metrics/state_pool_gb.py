"""Engine: bytes of the recurrent states the engine holds for its slots
(one float32 state a linear-attention layer a slot, whatever the slot's
length), from InferenceEngine.stats() at the counters' window's end, in GB.
None where the program has no such counter."""


def read(run):
    c = (run.get("counters") or {}).get("t1") or {}
    return c["state_pool_bytes"] / 1e9 if "state_pool_bytes" in c else None
