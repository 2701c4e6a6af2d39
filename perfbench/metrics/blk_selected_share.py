"""Model step: the positions the block-sparse layers' selection LEAVES a
decode row to attend (those up to the row's own of the blocks it selects)
over the positions live in its slot, summed over the decode rows of the
counters' window (delta blk_rows_read / delta blk_rows_live of
InferenceEngine.stats()), in per cent. Host arithmetic on the slots'
lengths: a property of the traffic and the selection rule, the least a
decode kernel could read of K and V, and NOT what the chip reads today:
`block_decode_attention` passes over a slot's whole 17,408 positions and
masks (PERF.md section 5 and 7). It moves only with the traffic until a
kernel reads the selected blocks in place; `decode_roofline_share.tok`
counts the same least. None where the program has no such counters."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        read_ = ml.counter_delta(run, "blk_rows_read")
        live = ml.counter_delta(run, "blk_rows_live")
    except KeyError:
        return None
    return read_ / live * 100.0 if live else None
