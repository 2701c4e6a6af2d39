"""Kernels, serving: of the layers whose prefill tile attends its scratch
by `_tile_attention` (a sliding layer's against its ring, a full layer's or
a hybrid block's heads against a cache by position), the share the Pallas
kernel of ops/tile_attention.py took, summed over the prefill dispatches of
the counters' window (delta tile_kernel_layers / delta tile_attn_layers of
InferenceEngine.stats()): 100 where every such tile's shapes fit the kernel
on a TPU, 0 where the XLA loop ran them all. Known when a tile program is
built, so it says which form the timed programs held, not how fast it was.
None where the program has no such counters (the parent's, a model none of
whose tiles goes that way) or the window held no prefill dispatch."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        took = ml.counter_delta(run, "tile_kernel_layers")
        layers = ml.counter_delta(run, "tile_attn_layers")
    except KeyError:
        return None
    return 100.0 * took / layers if layers else None
