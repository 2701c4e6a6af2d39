"""Engine: of the rows the tile programs held (a prefill tile and the
slots' decode rows behind it), the share that passed the layers that keep
no cache, summed over the prefill dispatches of the counters' window (delta
tail_rows_run / delta tile_rows of InferenceEngine.stats()), in per cent:
the rows a step samples alone pass them, 1 + n_slots of tile + n_slots;
100 would be every row through every layer. A decode-only step is not
counted: all its rows are sampled. None where the program has no such
counters (a model whose last layer keeps a cache) or the window held no
prefill dispatch."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        ran = ml.counter_delta(run, "tail_rows_run")
        held = ml.counter_delta(run, "tile_rows")
    except KeyError:
        return None
    return 100.0 * ran / held if held else None
