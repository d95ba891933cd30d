"""Prefill attention for latent-attention (MLA) layers in EXPANDED form:
one chunk of queries against a run of per-head keys and values whose
widths differ (192 and 128 for Kimi K2), as a forward-only flash kernel
that also returns the log-sum-exp, so that the runs a chunk meets (each
earlier chunk's latents, expanded a chunk at a time, then the chunk's own
keys, causal) are attended one kernel call each and merged outside
(:func:`merge`): float32 scores exist in VMEM only. The same chunk in
``jax.numpy`` (``ops.attention.paged_chunk_attention``) writes every
score to HBM three to four times, which at 64 heads x 8 rows x 1024
queries was over half of a prefill call's device time (PERF.md section 6,
PR 36).

Operands are bf16 with float32 accumulation (the probabilities are
rounded to bf16 for the second product, as the decode kernel's are).
Layout ``[N, H, S, D]``; the log-sum-exp leaves as lane-dense rows
``[N, H, 1, S]`` (``ops/attention.py``'s ``_row``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE, _row
from ray_tpu.ops.dispatch import interpret_default

BLOCK_Q, BLOCK_K = 256, 512


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, block_q, block_k, num_k_blocks):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: query i of the chunk sees key j <= i of the same chunk; a
    # key block wholly above the diagonal is skipped.
    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(iq * block_q + rows >= ik * block_k + cols, s,
                          DEFAULT_MASK_VALUE)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0]
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]       # the first key block is never skipped: l > 0
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _row(m_ref[:, :1] + jnp.log(l))


def attend_run(q, k, v, scale: float, *, causal: bool,
               interpret: Optional[bool] = None):
    """``softmax(q k^T * scale) v`` over ONE run of keys, and its
    log-sum-exp: q ``[N, H, S, Dk]``, k ``[N, H, T, Dk]``, v ``[N, H, T,
    Dv]`` -> (``[N, H, S, Dv]`` float32, ``[N, H, S]`` float32).
    ``causal`` (T == S): key j is seen by queries i >= j."""
    n, h, s, dk = q.shape
    t, dv = k.shape[2], v.shape[3]
    block_q, block_k = min(BLOCK_Q, s), min(BLOCK_K, t)
    if s % block_q or t % block_k or (causal and s != t):
        raise ValueError(f"queries {s} / keys {t} do not tile "
                         f"{block_q} x {block_k}")
    nk = t // block_k
    if interpret is None:
        interpret = interpret_default()

    def spec(rows, width, by_key):
        return pl.BlockSpec(
            (1, 1, rows, width),
            (lambda n_, h_, i, j: (n_, h_, j, 0)) if by_key
            else (lambda n_, h_, i, j: (n_, h_, i, 0)))

    out, lse = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk),
        grid=(n, h, s // block_q, nk),
        in_specs=[spec(block_q, dk, False), spec(block_k, dk, True),
                  spec(block_k, dv, True)],
        out_specs=[spec(block_q, dv, False),
                   pl.BlockSpec((1, 1, 1, block_q),
                                lambda n_, h_, i, j: (n_, h_, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((n, h, s, dv), jnp.float32),
                   jax.ShapeDtypeStruct((n, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        interpret=interpret,
        name="latent_prefill_attn",
        cost_estimate=pl.CostEstimate(
            flops=2 * n * h * s * t * (dk + dv) // (2 if causal else 1),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize
            + n * h * s * (dv + 1) * 4,
            transcendentals=n * h * s * t),
    )(q, k, v)
    return out, lse[:, :, 0]


def merge(a, b):
    """Two runs' ``(output, log-sum-exp)`` as the one softmax over both."""
    (oa, la), (ob, lb) = a, b
    both = jnp.logaddexp(la, lb)
    return (oa * jnp.exp(la - both)[..., None]
            + ob * jnp.exp(lb - both)[..., None]), both
