"""Model step: of the rows that passed an expert layer, the share with at
least one pick on an expert this rank holds, both programs, over the
counters' window (delta moe_rows_hit / delta moe_rows_real of
InferenceEngine.stats(), summed over the expert layers), in per cent: the
rows this chip would be SENT in the deployment. Where the routing is in
groups and the rank holds one group of eight with four taken, a row reaches
it only where its group is among the row's four (half the rows) and then
with 1 - C(192, 8) / C(256, 8) = 0.90: about 45; ungrouped top-8 of 512 onto
64 held would read 1 - (7 / 8)^8 = 66. A tile's rows are a request's own (a
padded tail is routed nowhere); a decode-only step routes every slot's row,
live or not, as `moe_local_picks` counts them. None where the program has
no such counters (the parent's, a model routed without groups)."""
from perfbench import metrics_lib as ml


def read(run):
    try:
        hit = ml.counter_delta(run, "moe_rows_hit")
        real = ml.counter_delta(run, "moe_rows_real")
    except KeyError:
        return None
    return 100.0 * hit / real if real else None
