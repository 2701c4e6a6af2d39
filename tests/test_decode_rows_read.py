"""What a decode row reads, said once (models/transformer.py
`decode_rows_read`): for each of the eight served families' small test model,
the counters `engine.stats()` has and their increments for a fixed list of
slot lengths, against closed forms. Host arithmetic: no program runs."""
import pytest

from ray_tpu.models import sparse_attention as sa
from ray_tpu.models.transformer import decode_rows_read

# five slots of 96 positions (key blocks of 32), before the rows' own
LENS = [0, 5, 23, 40, 70]
LIVE = 1 + 6 + 24 + 41 + 71         # each row attends itself too
# the XLA loop (the CPU's) passes over the blocks up to the LONGEST row's
# last live one for every row, the kernel over each row's own; and the
# row's own position
LOOP = 5 * (96 + 1)
KERNEL = (0 + 1) + (32 + 1) + (32 + 1) + (64 + 1) + (96 + 1)


def _cfg(family):
    if family in ("dense", "moe", "indexer"):
        from tests.test_fused_step import model_of
        return model_of(family)[0].cfg
    from tests import (test_falcon_h1_model, test_hybrid_mixer_model,
                       test_phi4flash_model, test_sarvam_model,
                       test_trinity_model)
    mod = {"blk_lin": test_hybrid_mixer_model, "hyb": test_falcon_h1_model,
           "win_att": test_trinity_model, "mla": test_sarvam_model,
           "diff": test_phi4flash_model}[family]
    return mod.build(mod.config()).cfg


@pytest.mark.parametrize("family,loop,kernel", [
    # K and V where they lie: Mistral's rows, and Mixtral's
    ("dense", dict(kv_rows_streamed=LOOP, kv_rows_live=LIVE),
     dict(kv_rows_streamed=KERNEL)),
    ("moe", dict(kv_rows_streamed=LOOP, kv_rows_live=LIVE),
     dict(kv_rows_streamed=KERNEL)),
    # Keye's: the selection leaves min(live, index_topk = 6) to attend
    ("indexer", dict(dsa_rows_read=1 + 6 + 6 + 6 + 6, dsa_rows_live=LIVE,
                     dsa_rows_streamed=LOOP),
     dict(dsa_rows_streamed=KERNEL)),
    # MiniCPM-SALA's "blk" layers: 6 blocks of 4, the row's own block among
    # them and holding the positions up to the row's only (live 41: its
    # block holds 1, so 5 * 4 + 1; live 71: 5 * 4 + 3)
    ("blk_lin", dict(blk_rows_read=1 + 6 + 24 + 21 + 23, blk_rows_live=LIVE),
     {}),
    # Falcon-H1's "hyb" layers are not counted
    ("hyb", {}, {}),
    # Trinity's "win" layers: a window of 16 in a ring of 24 (key blocks
    # of 8), which holds min(length, 24) of a slot and never the row's own
    ("win_att", dict(win_rows_streamed=5 * 24,
                     win_rows_live=1 + 6 + 16 + 16 + 16),
     dict(win_rows_streamed=0 + 8 + 24 + 24 + 24)),
    # sarvam's "mla" layers: the latents where they lie, one KV "head" of
    # latent + rope key, by their own kernel's predicate and block (32 of
    # 96 positions too)
    ("mla", dict(mla_rows_streamed=LOOP, mla_rows_live=LIVE),
     dict(mla_rows_streamed=KERNEL)),
    # Phi-4-mini-flash's small model: ONE cache by position read by its
    # "att" layer and the two "xat" layers behind it (three reads a
    # position), and three rings of 32 places under a window of 8 (96 =
    # 3 x 32: the blocks are 32 long, so a ring is one block whatever it
    # holds). Its rows read by `diff_attention.row_attention`'s loop on
    # any backend: no key changes where the kernels read
    ("diff", dict(xkv_rows_streamed=3 * LOOP, xkv_rows_live=3 * LIVE,
                  win_rows_streamed=5 * 32,
                  win_rows_live=1 + 6 + 8 + 8 + 8), {}),
], ids=["dense", "moe", "indexer", "blk_lin", "hyb", "win_att", "mla",
        "diff"])
def test_the_counters_of_a_family_and_their_closed_forms(family, loop,
                                                          kernel,
                                                          monkeypatch):
    """The keys are exactly the `*_rows_*` keys `engine.stats()` gave for
    the family on PR 48's tree (where engine.py held them by kind), no
    rows give zeros of the same keys, and the increments add up."""
    read = decode_rows_read(_cfg(family), 96)
    assert read(LENS) == loop
    assert read([]) == dict.fromkeys(loop, 0)
    # where the Pallas kernel reads (a TPU, shapes that fit it)
    monkeypatch.setattr(sa, "_kernel_reads", lambda M, Hkv, D: True)
    monkeypatch.setattr(sa, "_latent_row_kernel_takes",
                        lambda M, H, W, R: True)
    assert read(LENS) == {**loop, **kernel}
    # each row alone, summed, is what the rows give together there (but
    # what a loop streams: the longest row's blocks for every row)
    alone = [read([n]) for n in LENS]
    together = read(LENS)
    for k in loop:
        if k in kernel or not k.endswith("_streamed"):
            assert sum(a[k] for a in alone) == together[k], k
