"""Attention kernels.

`flash_attention` is a Pallas TPU kernel pair (tiled online-softmax forward
+ FlashAttention-2-style backward, VMEM-blocked for the MXU; see
/opt/skills/guides/pallas_guide.md conventions) wired up as a
`jax.custom_vjp`, so it is usable inside `jax.grad` train steps. Head dims
that aren't lane-aligned (e.g. 64) are zero-padded to 128 outside the
custom_vjp — padding q/k with zeros leaves the logits unchanged and AD
slices the gradients back. `flash_attention` always runs the kernel: it
raises on lengths or blocks the kernel cannot tile (`flash_fits` says
which), and off the TPU the kernel only runs with `interpret=True`. The
choice between this kernel and `mha_reference` — by platform and shape —
belongs to `ops/dispatch.py` `attention(impl="auto")` alone.

The reference framework has no attention kernels at all (it orchestrates
torch models); these exist because long-context parallelism is first-class
here (SURVEY.md §5.7).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def mha_reference(q, k, v, causal: bool = True,
                  q_offset: int = 0, k_offset: int = 0,
                  scale: Optional[float] = None):
    """XLA attention: q[B,Lq,H,D], k/v[B,Lk,Hkv,D] -> [B,Lq,H,D].
    Supports GQA (H a multiple of Hkv) and absolute position offsets for
    block-parallel callers."""
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    scale = scale if scale is not None else D ** -0.5
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = jnp.arange(Lq) + q_offset
        kpos = jnp.arange(Lk) + k_offset
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


# --------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                Lk: int, causal: bool, scale: float, block_q: int):
    qi = pl.program_id(1)
    q = q_ref[...]                      # [block_q, D]
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    n_kblocks = Lk // block_k

    def body(ki, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    if causal:
        # only blocks up to (and including) the diagonal contribute
        hi = jax.lax.min(n_kblocks, (qi + 1) * block_q // block_k + 1)
    else:
        hi = n_kblocks
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc, m, l))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


# -------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, block_k: int, Lk: int, causal: bool, scale: float,
                   block_q: int):
    qi = pl.program_id(1)
    q = q_ref[...]                          # [block_q, D]
    do = do_ref[...]
    lse = lse_ref[...]                      # [block_q, 1] f32
    delta = delta_ref[...]
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    n_kblocks = Lk // block_k

    def body(ki, acc):
        k = k_ref[pl.ds(ki * block_k, block_k), :]
        v = v_ref[pl.ds(ki * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                # [block_q, block_k]
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return acc + jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32) * scale

    if causal:
        hi = jax.lax.min(n_kblocks, (qi + 1) * block_q // block_k + 1)
    else:
        hi = n_kblocks
    acc = jax.lax.fori_loop(0, hi, body, acc)
    dq_ref[...] = acc.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q: int, Lq: int, causal: bool,
                    scale: float, block_k: int):
    ki = pl.program_id(1)
    k = k_ref[...]                          # [block_k, D]
    v = v_ref[...]
    D = k.shape[-1]
    dk = jnp.zeros((k.shape[0], D), jnp.float32)
    dv = jnp.zeros((k.shape[0], D), jnp.float32)
    n_qblocks = Lq // block_q

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[pl.ds(qi * block_q, block_q), :]
        do = do_ref[pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[pl.ds(qi * block_q, block_q), :]
        delta = delta_ref[pl.ds(qi * block_q, block_q), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)                # [block_q, block_k]
        dv = dv + jnp.dot(p.astype(do.dtype).T, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jnp.dot(ds.astype(q.dtype).T, q,
                          preferred_element_type=jnp.float32) * scale
        return dk, dv

    # causal: q blocks strictly before this k block contribute nothing
    lo = (ki * block_k) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(lo, n_qblocks, body, (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# ------------------------------------------------- custom_vjp core (BH,L,D)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_core(causal, block_q, block_k, scale, interpret, qf, kf, vf):
    o, _ = _flash_fwd(causal, block_q, block_k, scale, interpret,
                      qf, kf, vf)
    return o


def _flash_fwd(causal, block_q, block_k, scale, interpret, qf, kf, vf):
    BH, Lq, D = qf.shape
    _, Lk, _ = kf.shape
    kernel = functools.partial(_fwd_kernel, block_k=block_k, Lk=Lk,
                               causal=causal, scale=scale, block_q=block_q)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, Lq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Lk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return o, (qf, kf, vf, o, lse)


def _flash_bwd(causal, block_q, block_k, scale, interpret, res, do):
    qf, kf, vf, o, lse = res
    BH, Lq, D = qf.shape
    _, Lk, _ = kf.shape
    # delta_i = rowsum(dO_i * O_i) — cheap, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, block_k=block_k, Lk=Lk, causal=causal, scale=scale,
        block_q=block_q)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, Lq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, D), qf.dtype),
        interpret=interpret,
    )(qf, kf, vf, do, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, block_q=block_q, Lq=Lq, causal=causal, scale=scale,
        block_k=block_k)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, Lk // block_k),
        in_specs=[
            pl.BlockSpec((None, Lq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Lq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Lq, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Lq, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), kf.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), vf.dtype),
        ],
        interpret=interpret,
    )(qf, kf, vf, do, lse, delta)
    return dq, dk, dv


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------ public entry
def flash_fits(Lq: int, Lk: int, block_q: int = 256,
               block_k: int = 256) -> bool:
    """Whether the kernel can tile these lengths: whole 128-row tiles,
    and blocks (clamped to the length) that divide it and each other."""
    block_q, block_k = min(block_q, Lq), min(block_k, Lk)
    return not (Lq % 128 or Lk % 128 or Lq % block_q or Lk % block_k
                or block_q % block_k)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, scale: Optional[float] = None,
                    interpret: bool = False):
    """Tiled attention, differentiable. q[B,Lq,H,D], k/v[B,Lk,Hkv,D]
    (GQA ok). Head dim is zero-padded up to a multiple of 128 lanes.
    Always the Pallas kernel, never the reference: raises ValueError on
    lengths it cannot tile."""
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    scale = scale if scale is not None else D ** -0.5
    if not flash_fits(Lq, Lk, block_q, block_k):
        raise ValueError(
            f"flash attention cannot tile Lq={Lq}, Lk={Lk} with blocks "
            f"({block_q}, {block_k}): lengths must be multiples of 128 "
            f"and of their block, and block_q of block_k")
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    Dp = (D + 127) // 128 * 128
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # layout: [B*H, L, D] so each grid cell works on one head's q block
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, Dp)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, Dp)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, Dp)
    out = _flash_core(causal, block_q, block_k, scale, interpret,
                      qf, kf, vf)
    out = out.reshape(B, H, Lq, Dp).transpose(0, 2, 1, 3)
    return out[..., :D] if Dp != D else out
