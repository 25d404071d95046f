"""Pallas TPU flash attention (forward) for the LM substrate.

Blocked online-softmax attention: grid over (batch*heads, q_blocks); each
step streams K/V blocks through VMEM, maintaining running max / sum /
accumulator. This is the explicit-VMEM version of the ``_sdpa_chunked``
pure-JAX path in ``models/layers.py`` (which XLA targets today); the kernel
is validated against the oracle in interpret mode and is the drop-in for
real-TPU prefill/train once past the dry-run stage.

Layout: q (BH, S, hd), k/v (BH, T, hd) with GQA repetition done by the
caller (ops.flash_attention handles the reshapes). Block sizes are
hardware-aligned (q_blk x hd and k_blk x hd tiles, hd in {64,80,128,256}).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *,
                      k_blk: int, causal: bool, scale: float):
    # q_ref: (1, q_blk, hd); k_ref/v_ref: (1, T, hd); o_ref: (1, q_blk, hd)
    q = q_ref[0].astype(jnp.float32) * scale          # (q_blk, hd)
    q_blk, hd = q.shape
    T = k_ref.shape[1]
    qi = pl.program_id(1)
    q_pos = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, k_blk),
                                                  0)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * k_blk, k_blk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * k_blk, k_blk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            k_pos = i * k_blk + jax.lax.broadcasted_iota(
                jnp.int32, (q_blk, k_blk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    n_k = T // k_blk
    m0 = jnp.full((q_blk,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q_blk,), jnp.float32)
    a0 = jnp.zeros((q_blk, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_blk", "k_blk",
                                             "interpret"))
def flash_attention_bh(q, k, v, *, causal: bool = True, q_blk: int = 512,
                       k_blk: int = 512, interpret: bool = False):
    """q (BH, S, hd), k/v (BH, T, hd) -> (BH, S, hd)."""
    BH, S, hd = q.shape
    T = k.shape[1]
    q_blk = min(q_blk, S)
    k_blk = min(k_blk, T)
    assert S % q_blk == 0 and T % k_blk == 0
    grid = (BH, S // q_blk)
    kern = functools.partial(_flash_fwd_kernel, k_blk=k_blk, causal=causal,
                             scale=1.0 / np.sqrt(hd))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_blk, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False,
                    q_blk: int = 512, k_blk: int = 512):
    """q (B, S, H, hd), k/v (B, T, KV, hd) with KV | H (GQA)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kb = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    ob = flash_attention_bh(qb, kb, vb, causal=causal, q_blk=q_blk,
                            k_blk=k_blk, interpret=interpret)
    return ob.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
