"""Pallas TPU kernel: causal flash attention (online softmax), GQA-ready.

The jnp blockwise implementation (`models/attention.py::_blockwise_core`)
is the oracle; this kernel is the TPU-native form: one (q-block) VMEM tile
per grid step, KV streamed in `block_k` chunks with the running
(max, sum, acc) carried in registers.  MXU-aligned block shapes; heads are
folded into the grid's leading axis so GQA layouts reuse the same kernel
(ops.py broadcasts KV heads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, scale: float,
            causal: bool):
    bq, dh = q_ref.shape
    t = k_ref.shape[0]
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T                    # (bq, bk)
        if causal:
            q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(k_idx <= q_idx, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + p @ v.astype(jnp.float32)
        return acc_new, m_new, l_new

    n_blocks = t // block_k
    if causal:
        # only KV blocks up to this q block contribute
        n_blocks = jnp.minimum(n_blocks, (qi + 1) * bq // block_k
                               + (1 if bq % block_k or True else 0))
        n_blocks = jnp.minimum(n_blocks, t // block_k)
    acc0 = jnp.zeros((bq, dh), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           block_q: int = 128, block_k: int = 128,
                           causal: bool = True,
                           interpret: bool = False) -> jax.Array:
    """q: (BH, S, Dh); k/v: (BH, T, Dh); S % block_q == T % block_k == 0."""
    bh, s, dh = q.shape
    t = k.shape[1]
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = 1.0 / (dh ** 0.5)
    grid = (bh, s // block_q)
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, scale=scale,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, t, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, t, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        interpret=interpret,
    )(q, k, v)
