"""Attention ops: JAX reference MHA/GQA + pallas TPU flash attention.

The reference framework has no attention kernels of its own (it hosts engines
that bring them — SURVEY.md §2.3); a TPU-native training/serving framework
must supply them. Design follows the blockwise online-softmax scheme
(Flash Attention) tiled for the MXU:

* forward: grid ``(batch, q_heads, q_blocks, k_blocks)`` — the innermost grid
  dimension runs sequentially on TPU, so the running max / sum / accumulator
  live in VMEM scratch carried across k-blocks.
* backward: one pass for dq (grid over k inside), one for dk/dv (grid over q
  inside), with the standard ``delta = rowsum(dO * O)`` precomputation.
* GQA is expressed in the BlockSpec index maps (kv head = q head // group) —
  K/V are never materialized per-q-head.

Public entry :func:`flash_attention` is shape-polymorphic over GQA and
dispatches to the pallas kernel on TPU, and to the fused-by-XLA reference
implementation elsewhere (CPU tests run the kernel in interpret mode).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.dispatch import interpret_default

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Reference implementation (also the CPU path; XLA fuses it adequately there).
# ---------------------------------------------------------------------------

def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Plain attention. q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] (GQA ok)."""
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None, None], logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash attention (TPU)
# ---------------------------------------------------------------------------

# Per-query statistics (the log-sum-exp, the backward's delta) cross HBM as
# lane-dense ROWS ``[B, H, 1, S]``. As columns ``[B, H, S, 1]`` every float
# is padded to a 128-lane tile: 67 MB where 0.5 MB are data at 4 x 16 x
# 2048, which the kernels write and read block by block and which XLA
# reads or writes whole wherever it reshapes one (a saved residual, PR 35).
# Inside a kernel the softmax wants a column beside its [bq, bk] scores;
# the turn is a transpose of a 128-wide tile, once a grid step.

def _row(col):
    """``[n, 1]`` -> ``[1, n]``."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _column(row):
    """``[1, n]`` -> ``[n, 1]``."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


# A block step's body depends on where the block stands to the causal
# diagonal, which the kernel knows from its grid position (row ``r`` sees
# the columns up to ``r + offs``; ``offs = sk - sq`` aligns the mask
# bottom-right, as mha_reference's tril(k=sk-sq), so sq != sk works).
# ABOVE the diagonal nothing runs, and no block is fetched for the
# backward kernels. INTERIOR, every score live: no mask is built. ON it:
# the backward kernels, which the MXU paces, cut the block ``_DIAG_SPLIT``
# x ``_DIAG_SPLIT`` and multiply only the sub-tiles that hold a live
# score, masking those the diagonal crosses; the forward, which its
# softmax chain paces (max before exp before the second product), takes
# the block whole under a mask the compiler folds. Operands enter the
# products in the inputs' dtype (bf16: one MXU pass, float32 out); max,
# sum, exp and the accumulators are float32; ``scale`` rides in the
# multiply an exp has anyway: exp(scale s - m) = exp2((s - m') scale
# log2 e).
_DIAG_SPLIT = 4
_LOG2E = math.log2(math.e)
_NT = (((1,), (1,)), ((), ()))        # a @ b.T, float32 out


def _causal_step(iq, ik, block_q, block_k, offs):
    """``(run, interior)`` of block step ``(iq, ik)``: whether any of its
    scores is live, and whether all are. Ints or traced scalars."""
    first_row, first_col = iq * block_q + offs, ik * block_k
    return (first_col <= first_row + (block_q - 1),
            first_col + (block_k - 1) <= first_row)


def _aligned(block_q, block_k, offs):
    """Whether the diagonal runs through the corners of the blocks it
    crosses (square blocks, ``offs`` a whole number of them): the mask of
    such a block is static."""
    return block_q == block_k and offs % block_k == 0


def _diag_split(block_q, block_k, offs):
    """Sub-tiles a side that the backward kernels cut a block ON the
    diagonal into: the cut is static, so the block is aligned, and a
    sub-tile is whole 128-lane tiles; else 1."""
    return _DIAG_SPLIT if (_aligned(block_q, block_k, offs)
                           and block_k % (128 * _DIAG_SPLIT) == 0) else 1


def _block_step(step, iq, ik, *, causal, block_q, block_k, offs, n=1,
                by_rows=True):
    """Run ``step(bands, shift)`` over the live part of block step ``(iq,
    ik)``. ``bands`` lists ``(band, parts)``: a slice of the block's rows
    (``by_rows``; else of its columns) and the slices of the other axis it
    is multiplied with, the sub-tiles under the diagonal as ONE part.
    With a ``shift`` a band's LAST part is crossed by the diagonal: its
    row ``r`` sees its columns up to ``r + shift`` (0 where the diagonal
    runs through the block's corner, else traced, the block one piece)."""
    own, other = (block_q, block_k) if by_rows else (block_k, block_q)
    t, u = own // n, other // n

    def band(i):
        under = slice(0, i * u) if by_rows else slice((i + 1) * u, other)
        return (slice(i * t, (i + 1) * t),
                ([under] if under.start < under.stop else [])
                + [slice(i * u, (i + 1) * u)])

    whole = [(slice(0, own), [slice(0, other)])]
    if not causal:
        return step(whole, None)
    run, interior = _causal_step(iq, ik, block_q, block_k, offs)
    shift = (0 if _aligned(block_q, block_k, offs)
             else iq * block_q + offs - ik * block_k)
    pl.when(interior)(lambda: step(whole, None))
    pl.when(jnp.logical_and(run, jnp.logical_not(interior)))(
        lambda: step([band(i) for i in range(n)], shift))


def _scores(a, b, shift, q_axis=0):
    """Float32 ``a @ b.T``, the dead scores at the mask value: queries lie
    along ``q_axis`` and query ``r`` sees keys up to ``r + shift``."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if shift is None:
        return s
    q = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q + shift >= k, s, DEFAULT_MASK_VALUE)


def flash_block_steps(sq, sk, block_q, block_k, causal=True):
    """The kernels' schedule a (batch, head), which is static: ``(skipped,
    interior, diagonal, share)``: the block steps above, under and on the
    causal diagonal, and the share of the RUN steps' sub-tiles that the
    backward kernels multiply (the forward multiplies a run step whole).
    1 | 1 | 2 of 4 at 2048 and 6 | 6 | 4 of 16 at 4096 in 1024-blocks."""
    steps = [_causal_step(iq, ik, block_q, block_k, sk - sq) if causal
             else (True, True)
             for iq in range(sq // block_q) for ik in range(sk // block_k)]
    interior = sum(i for _, i in steps)
    diagonal = sum(r and not i for r, i in steps)
    n = _diag_split(block_q, block_k, sk - sq)      # n (n + 1) / 2 of n x n
    share = (interior + diagonal * (n + 1) / (2 * n)) / max(
        interior + diagonal, 1)
    return len(steps) - interior - diagonal, interior, diagonal, share


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, num_k_blocks, offs):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(_, shift):                                      # the block whole
        v = v_ref[0, 0]                                      # [bk, d]
        s = _scores(q_ref[0, 0], k_ref[0, 0], shift)         # [bq, bk], unscaled
        m_prev = m_ref[:, :1]                                # [bq, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2((s - m_new) * (scale * _LOG2E))         # [bq, bk]
        alpha = jnp.exp2((m_prev - m_new) * (scale * _LOG2E))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _block_step(step, iq, ik, causal=causal, block_q=block_q,
                block_k=block_k, offs=offs)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        # Fully-masked rows (possible with padding) have l == 0; emit zeros.
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = _row(m_ref[:, :1] * scale + jnp.log(safe_l))


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    nq, nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)

    grid = (b, hq, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h, i, j: (b_, h // group, j, 0))
    out_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    # lse leaves as rows [B, H, 1, S]: block last-two dims (1, block_q)
    # satisfy the TPU tiling rule (sublane == full array dim, lane a
    # multiple of 128 or the full dim).
    lse_spec = pl.BlockSpec((1, 1, 1, block_q), lambda b_, h, i, j: (b_, h, 0, i))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=nk, offs=sk - sq,
    )
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
    ]
    _, interior, diagonal, _ = flash_block_steps(sq, sk, block_q, block_k,
                                                 causal)
    scores = b * hq * (interior + diagonal) * block_q * block_k    # it runs
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[out_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",
        cost_estimate=pl.CostEstimate(
            flops=4 * scores * d,
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=scores,
        ),
    )(q, k, v)
    return out, lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
               *, scale, causal, block_q, block_k, num_k_blocks, offs):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(bands, shift):
        for rows, parts in bands:
            q, do = q_ref[0, 0, rows], do_ref[0, 0, rows]     # [tq, d]
            lse = _column(lse_ref[0, 0, :, rows]) * _LOG2E    # [tq, 1]
            delta = _column(delta_ref[0, 0, :, rows])
            for cols in parts:
                k, v = k_ref[0, 0, cols], v_ref[0, 0, cols]   # [tk, d]
                s = _scores(q, k, shift if cols is parts[-1] else None)
                p = jnp.exp2(s * (scale * _LOG2E) - lse)      # [tq, tk]
                dp = jax.lax.dot_general(
                    do, v, _NT, preferred_element_type=jnp.float32)
                ds = (p * (dp - delta)).astype(k.dtype)
                acc_ref[rows] += jnp.dot(
                    ds, k, preferred_element_type=jnp.float32)

    _block_step(step, iq, ik, causal=causal, block_q=block_q, block_k=block_k,
                offs=offs, n=_diag_split(block_q, block_k, offs))

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, block_q, block_k, num_q_blocks, offs):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Scores, p and ds are made TRANSPOSED, [tk, tq]: the two products
    # that contract over queries then take them as they are (no turn of a
    # [tq, tk] tile through the transpose unit), and lse and delta are
    # used as the rows they arrive as.
    def step(bands, shift):
        for cols, parts in bands:
            k, v = k_ref[0, 0, cols], v_ref[0, 0, cols]       # [tk, d]
            for rows in parts:
                q, do = q_ref[0, 0, rows], do_ref[0, 0, rows]  # [tq, d]
                lse = lse_ref[0, 0, :, rows] * _LOG2E          # [1, tq]
                delta = delta_ref[0, 0, :, rows]
                s = _scores(k, q, shift if rows is parts[-1] else None, 1)
                p = jnp.exp2(s * (scale * _LOG2E) - lse)       # [tk, tq]
                dp = jax.lax.dot_general(
                    v, do, _NT, preferred_element_type=jnp.float32)
                ds = (p * (dp - delta)).astype(q.dtype)
                dv_acc[cols] += jnp.dot(p.astype(do.dtype), do,
                                        preferred_element_type=jnp.float32)
                dk_acc[cols] += jnp.dot(ds, q,         # `scale`: finalize
                                        preferred_element_type=jnp.float32)

    _block_step(step, iq, ik, causal=causal, block_q=block_q, block_k=block_k,
                offs=offs, n=_diag_split(block_q, block_k, offs), by_rows=False)

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(res, g, *, scale, causal, block_q, block_k, interpret):
    q, k, v, out, lse = res
    do = g
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    nq, nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)

    # Rows [B, H, 1, S], as the forward kernel wrote lse.
    lse = lse[:, :, None, :]
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    # A step above the diagonal asks for the block its row's last run step
    # held (its column's first): an index that does not move fetches
    # nothing, where a skipped step's 0.5 MB of blocks took longer than
    # the step.
    def last_k(i, j):
        return jnp.minimum(j, (i * block_q + block_q - 1 + sk - sq)
                           // block_k) if causal else j

    def first_q(j, i):
        return jnp.maximum(i, (j * block_k - (sk - sq)) // block_q
                           ) if causal else i

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0))
    kv_spec_dq = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b_, h, i, j: (b_, h // group, last_k(i, j), 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q), lambda b_, h, i, j: (b_, h, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          offs=sk - sq),
        grid=(b, hq, nq, nk),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: grid over q-heads; each q-head contributes to its kv head. To
    # keep the accumulation race-free we compute per-q-head dk/dv and sum the
    # group afterwards (cheap: [b, hq, sk, d] f32 intermediate).
    q_spec2 = pl.BlockSpec((1, 1, block_q, d),
                           lambda b_, h, j, i: (b_, h, first_q(j, i), 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, h, j, i: (b_, h // group, j, 0))
    kv_out_spec = pl.BlockSpec((1, 1, block_k, d),
                               lambda b_, h, j, i: (b_, h, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, 1, block_q),
                             lambda b_, h, j, i: (b_, h, 0, first_q(j, i)))

    dk_ph, dv_ph = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          offs=sk - sq),
        grid=(b, hq, nk, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    dk = dk_ph.reshape(b, hkv, group, sk, d).sum(axis=2).astype(k.dtype)
    dv = dv_ph.reshape(b, hkv, group, sk, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    # Named so a remat policy can KEEP the kernel's two outputs
    # (FLASH_RESIDUAL_NAMES): they come out of a pallas_call, not a dot, so
    # a dots-saveable policy alone drops them and the backward of a
    # checkpointed layer runs this whole kernel a second time to get them
    # back. Outside a jax.checkpoint a name is the identity. lse is kept
    # as [B, H, S], the kernel's rows less their unit axis: 0.5 MB a layer
    # at 4 x 16 x 2048, and no relayout on the way in or out.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse[:, :, 0, :], "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, res, g):
    return _bwd(res, g, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)

# What _flash_fwd_rule names: a jax.checkpoint policy that saves these
# (``save_only_these_names(*FLASH_RESIDUAL_NAMES)``) has no flash forward in
# its backward, for B x S x H x (2 D + 4) bytes a call.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def flash_applicable(
    sq: int, sk: int, d: int, *, causal: bool = True,
    block_q: int = 1024, block_k: int = 1024,
) -> bool:
    """True when :func:`flash_attention` takes the pallas kernel path for
    these shapes (vs the XLA reference fallback). Kept next to the kernel so
    diagnostics (bench.py) can't drift from the real dispatch predicate."""
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    return not (
        sq < 8 or sq % block_q or sk % block_k or d % 128
        or (causal and sq > sk)
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention. Layout [B, S, H, D]; supports GQA (Hkv divides Hq).

    Falls back to :func:`mha_reference` when the sequence doesn't tile
    (shorter than one block) — XLA handles those sizes well natively.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if not flash_applicable(sq, sk, d, causal=causal,
                            block_q=block_q, block_k=block_k):
        # Tiny-q (decode), non-tiling shapes, or causal-with-fewer-keys (rows
        # would be fully masked): XLA handles these well natively.
        return mha_reference(q, k, v, causal=causal, scale=scale)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if interpret is None:
        # RAY_TPU_PALLAS_INTERPRET overrides (the pallas_interpret test
        # fixture), else interpret everywhere but real TPU.
        interpret = interpret_default()

    # Kernels use [B, H, S, D].
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, scale, causal, block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Chunked prefill: one chunk of queries over the paged cache's earlier keys
# ---------------------------------------------------------------------------

def paged_chunk_attention(q, k_new, v_new, arena_k, arena_v, layer, tables,
                          first_pos: int, chunk_pos: int, scale: float, *,
                          window: int = 0, key_blocks: int = 4,
                          key_step: int = 256, expand=None):
    """Causal attention of ONE CHUNK of a prompt: queries ``q [N, S, Hq,
    D]`` at absolute positions ``chunk_pos + arange(S)`` over the
    prompt's earlier keys, which lie in a paged cache, and then over the
    chunk's own ``k_new``/``v_new [N, S, KVH, D]``. Blockwise online
    softmax in ``jax.numpy``: float32 scores exist for ``key_step`` keys
    at a time, never for the whole context (which ``[N, S, Hq, P + S]``
    float32 would be: 4.3 GB for 8 x 1024 queries of 32 heads over 4096
    keys); the products run on bf16 operands with float32 accumulation.

    ``arena_k``/``arena_v [L, NB, KVH, bs, D]`` read at ``layer`` (a
    traced scalar; one gather a step, no slab is sliced); ``tables [N,
    m]`` names, for each row, the blocks that hold positions
    ``first_pos .. first_pos + m * bs`` in order (``m`` may be 0: the
    first chunk). With ``window`` a query sees only its last ``window``
    keys, itself included; the caller then lists only blocks that hold
    one (a ring's live entries), so blocks out of range cost nothing.

    ``expand`` (a LATENT cache, ``models/mla.py``): the cache holds no
    per-head K/V; ``expand(arena_k[layer, idx]) -> (keys, values)`` makes
    them from a step's gathered blocks ``[N, g, ...]``, ``[N, g * bs,
    KVH, D]`` and ``[N, g * bs, KVH, Dv]``, so they exist for one step's
    keys at a time (``arena_v`` is then not read). The values' width may
    differ from the keys'.
    Returns ``[N, S, Hq, Dv]`` in ``q``'s dtype."""
    n, s, hq, d = q.shape
    hkv = k_new.shape[2]
    dv = v_new.shape[-1]
    bs = arena_k.shape[3]
    qg = q.reshape(n, s, hkv, hq // hkv, d)
    q_pos = chunk_pos + jnp.arange(s)

    def attend(carry, kb, vb, k_pos):
        """One online-softmax step over keys ``kb``/``vb [N, T, KVH, D]``
        at positions ``k_pos [T]``."""
        m, l, acc = carry
        sc = jnp.einsum("nqhgd,nkhd->nqhgk", qg, kb.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
        seen = q_pos[:, None] >= k_pos[None, :]
        if window:
            seen &= q_pos[:, None] - k_pos[None, :] < window
        seen = seen[None, :, None, None, :]
        sc = jnp.where(seen, sc, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        # A step may hide every key from a query (m_new stays at the
        # mask value, where exp(sc - m_new) would be 1): select, too.
        p = jnp.where(seen, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "nqhgk,nkhd->nqhgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    lead = qg.shape[:-1]
    carry = (jnp.full(lead, DEFAULT_MASK_VALUE, jnp.float32),
             jnp.zeros(lead, jnp.float32),
             jnp.zeros(lead + (dv,), jnp.float32))

    m_blocks = tables.shape[1]
    if m_blocks:
        g = math.gcd(m_blocks, key_blocks)       # blocks a step

        def blocks(arena, idx):
            # [N, g, KVH, bs, D] -> [N, g * bs, KVH, D]
            b = jnp.swapaxes(arena[layer, idx], 2, 3)
            return b.reshape(n, g * bs, hkv, d)

        def from_cache(carry, step):
            i, idx = step
            k_pos = first_pos + i * (g * bs) + jnp.arange(g * bs)
            kb, vb = (expand(arena_k[layer, idx]) if expand else
                      (blocks(arena_k, idx), blocks(arena_v, idx)))
            return attend(carry, kb, vb, k_pos), None

        steps = m_blocks // g
        carry, _ = jax.lax.scan(
            from_cache, carry,
            (jnp.arange(steps),
             jnp.swapaxes(tables.reshape(n, steps, g), 0, 1)))

    t = math.gcd(s, key_step)                    # own keys a step

    def from_chunk(carry, step):
        i, kb, vb = step
        return attend(carry, kb, vb, chunk_pos + i * t + jnp.arange(t)), None

    def stepped(a):
        return jnp.swapaxes(a.reshape(n, s // t, t, hkv, a.shape[-1]), 0, 1)

    (_, l, acc), _ = jax.lax.scan(
        from_chunk, carry, (jnp.arange(s // t), stepped(k_new),
                            stepped(v_new)))
    return (acc / l[..., None]).reshape(n, s, hq, dv).astype(q.dtype)
