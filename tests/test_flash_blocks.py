"""The flash kernels' walk over blocks (ops/attention.py), on the CPU: the
shared bound function against a brute-force reading of the causal
triangle, the block counts at the training cell's shape, the blocks chosen
from a shape, and parity of forward and gradients with `mha_reference` in
interpret mode at block shapes the single 128 x 128 case never met."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (causal_block_range, choose_blocks,
                                   flash_attention, flash_block_counts,
                                   flash_fits, mha_reference)

# (Lq, Lk, block_q, block_k)
WALKS = {
    "cell-4096-256x256": (4096, 4096, 256, 256),
    "bq-gt-bk": (2048, 2048, 512, 128),
    "bq-lt-bk": (2048, 2048, 128, 512),
    "one-block": (128, 128, 128, 128),
    "bq-not-multiple-of-bk": (1536, 1536, 384, 256),
    "Lq-lt-Lk": (512, 1024, 128, 256),
    "Lq-gt-Lk": (1024, 512, 256, 128),
}


def _brute(Lq, Lk, bq, bk, causal):
    """Per (Q block, K block): 'interior' if every element is visible,
    'masked' if some are, None if none is (row >= column)."""
    kind = {}
    for qi in range(Lq // bq):
        for ki in range(Lk // bk):
            rows = np.arange(qi * bq, (qi + 1) * bq)[:, None]
            cols = np.arange(ki * bk, (ki + 1) * bk)[None, :]
            vis = (rows >= cols) if causal else np.ones((bq, bk), bool)
            kind[qi, ki] = ("interior" if vis.all() else
                            "masked" if vis.any() else None)
    return kind


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("walk", ["k", "q"])
@pytest.mark.parametrize("case", list(WALKS.values()), ids=list(WALKS))
def test_walk_meets_exactly_the_triangle(case, walk, causal):
    Lq, Lk, bq, bk = case
    kind = _brute(Lq, Lk, bq, bk, causal)
    n_q, n_k = Lq // bq, Lk // bk
    n_outer, n_inner = (n_q, n_k) if walk == "k" else (n_k, n_q)
    for i in range(n_outer):
        (m0, m1), (i0, i1) = causal_block_range(walk, i, bq, bk, n_inner,
                                                causal)
        for j in range(n_inner):
            want = kind[(i, j) if walk == "k" else (j, i)]
            got = ("masked" if m0 <= j < m1 else
                   "interior" if i0 <= j < i1 else None)
            assert got == want, (walk, i, j)
    if not causal:
        counts = flash_block_counts(walk, Lq, Lk, bq, bk, causal=False)
        assert counts == {"visited": n_q * n_k, "masked": 0,
                          "needed": n_q * n_k}


def test_the_walk_traces_as_it_counts():
    """A traced block index (the kernels' `program_id`) gives the ranges a
    Python int gives."""
    fn = jax.jit(lambda i: causal_block_range("k", i, 512, 128, 16))
    for i in range(4):
        got = jax.tree.map(int, fn(jnp.int32(i)))
        assert got == causal_block_range("k", i, 512, 128, 16)


@pytest.mark.parametrize("walk", ["k", "q"])
def test_block_counts_at_the_training_cells_shape(walk):
    """L 4096 at 256 x 256: 136 of a head's 256 blocks touch the triangle
    and 16 lie on the diagonal; the parent's forward visited 151 and
    masked every one of them (PERF.md section 6, PR 31)."""
    assert flash_block_counts(walk, 4096, 4096, 256, 256) == {
        "visited": 136, "masked": 16, "needed": 136}


@pytest.mark.parametrize("walk", ["k", "q"])
def test_chosen_blocks_visit_only_what_is_needed(walk):
    """At the cell's shape the chosen blocks (1024 x 1024, computed in
    512 x 512 tiles) compute the 36 tiles the triangle touches and mask
    the 8 on the diagonal."""
    bq, bk = choose_blocks(4096, 4096, 128)
    assert flash_block_counts(walk, 4096, 4096, bq, bk) == {
        "visited": 36, "masked": 8, "needed": 36}


@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("L", [128, 256, 384, 1024, 4096, 8192])
def test_chosen_blocks_divide_the_length(L, D):
    bq, bk = choose_blocks(L, L, D)
    assert L % bq == 0 and L % bk == 0
    assert bq % 128 == 0 and bk % 128 == 0
    assert flash_fits(L, L) and flash_fits(L, L, bq, bk)
    if L <= 256:            # a short sequence is one (diagonal) block
        assert flash_block_counts("k", L, L, bq, bk) == {
            "visited": 1, "masked": 1, "needed": 1}


def test_pinning_one_block_alone_is_refused():
    x = jnp.zeros((1, 128, 1, 128), jnp.float32)
    with pytest.raises(ValueError, match="both"):
        flash_attention(x, x, x, block_q=128, interpret=True)


# (B, L or (Lq, Lk), H, Hkv, D, block_q, block_k); None = the blocks the
# kernels choose
PARITY = {
    "Lq-lt-Lk": (1, (256, 512), 1, 1, 128, 128, 128),
    "Lq-gt-Lk": (1, (512, 256), 1, 1, 128, 128, 128),
    "bq-gt-bk": (1, 512, 2, 2, 128, 256, 128),
    "bq-lt-bk": (1, 512, 2, 2, 128, 128, 256),
    "four-diagonal-blocks-a-q-block": (1, 1024, 1, 1, 128, 512, 128),
    "gqa-4-to-1": (2, 256, 8, 2, 64, 128, 128),
    "square-blocks-computed-in-tiles": (1, 1024, 1, 1, 128, 512, 512),
    "chosen-blocks-four-blocks-long": (1, 4096, 1, 1, 128, None, None),
    "chosen-blocks-one-block": (2, 128, 2, 1, 128, None, None),
}


@pytest.fixture(scope="module", params=list(PARITY.values()),
                ids=list(PARITY))
def parity(request):
    """Forward and all three gradients of the kernel (interpret mode) and
    of the reference, once a case."""
    B, L, H, Hkv, D, bq, bk = request.param
    Lq, Lk = L if isinstance(L, tuple) else (L, L)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(Lq + H), 4)
    q = jax.random.normal(k1, (B, Lq, H, D), jnp.float32)
    k = jax.random.normal(k2, (B, Lk, Hkv, D), jnp.float32)
    v = jax.random.normal(k3, (B, Lk, Hkv, D), jnp.float32)
    dout = jax.random.normal(k4, (B, Lq, H, D), jnp.float32)

    def run(attn):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out, *vjp(dout))

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True))
    want = run(lambda q, k, v: mha_reference(q, k, v, causal=True))
    return dict(zip(("out", "dq", "dk", "dv"), zip(got, want)))


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_parity_with_the_reference(parity, what):
    got, want = parity[what]
    tol = 2e-3 if what == "out" else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol, err_msg=what)
