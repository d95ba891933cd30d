"""Fused pallas decode attention: per-slot single-query GQA over a KV pool.

The continuous-batching decode tick attends ONE query token per slot
against that slot's cached prefix — the serving hot loop is pure HBM
bandwidth: read the KV prefixes once, emit [B, H, D]. The XLA reference
path (:func:`decode_attention_reference`, the engine's original
``_attend_decode``) upcasts the full ``[B, S_max, KVH, D]`` cache to fp32
and materializes it twice per layer (QK^T and PV see separate fp32
copies), tripling the bytes moved per tick. This kernel fuses the length
mask, online softmax, and PV product into one pass that streams K and V
through VMEM in their storage dtype (bf16 on TPU) with fp32 accumulation.

Grid ``(batch, k_blocks)`` with the innermost dimension sequential on
TPU so the running max / sum / accumulator live in VMEM scratch. Each
step streams one ``[block_k, KVH, D]`` stripe covering EVERY kv head:
the TPU lowering only accepts blocks whose last two dims are whole
(8, 128) tiles or the full array dims, so a block cannot slice one head
out of the ``KVH`` axis. Heads become the batch dim of the two MXU
contractions; GQA keeps each head's query group ``[G, D]`` resident
(G = Hq // Hkv), so K/V are read exactly once. Per-slot positions ride
scalar prefetch (SMEM) and gate both the block grid (blocks wholly past
a slot's position are skipped) and the in-block mask. The online-softmax
core (:func:`_init_state` / :func:`_attend_block` / :func:`_finalize`)
is shared with the paged kernel (``ops/paged_decode_attention.py``).

Dispatch: :func:`decode_attention` runs the kernel on TPU when the
shapes tile, interpret mode when forced (CPU tier-1 tests), and the XLA
reference otherwise. ``RAY_TPU_PALLAS_INTERPRET=1`` forces interpret
mode globally (the ``pallas_interpret`` conftest fixture).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

# The reference masks with -1e30 (not -inf: fully-masked garbage rows in
# inactive slots must softmax to finite values, not NaN). Kept identical
# here so kernel-on/off greedy decode stays token-for-token stable.
MASK_VALUE = -1e30


def env_flag(name: str) -> Optional[bool]:
    """Tri-state env knob: '1'/'true'/'on' -> True, '0'/'false'/'off' ->
    False, unset/other -> None (auto)."""
    val = os.environ.get(name, "").strip().lower()
    if val in ("1", "true", "on", "yes"):
        return True
    if val in ("0", "false", "off", "no"):
        return False
    return None


def _interpret_default() -> bool:
    forced = env_flag("RAY_TPU_PALLAS_INTERPRET")
    if forced is not None:
        return forced
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Reference (the engine's original _attend_decode; also the CPU path).
# ---------------------------------------------------------------------------

def decode_attention_reference(q, cache_k, cache_v, positions,
                               scale: Optional[float] = None):
    """Single-token attention with per-slot positions.

    q [B, H, D]; cache [B, S_max, KVH, D]; positions [B] (the absolute
    position each slot's query occupies).
    """
    b, hq, d = q.shape
    s_max, hkv = cache_k.shape[1], cache_k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg,
                        cache_k.astype(jnp.float32)) * scale
    slots = jnp.arange(s_max)
    mask = positions[:, None] >= slots[None, :]             # [B, S_max]
    logits = jnp.where(mask[:, None, None, :], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs,
                     cache_v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _init_state(acc_ref, m_ref, l_ref):
    m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _attend_block(q, k, v, pos, first_col, acc_ref, m_ref, l_ref, *, scale,
                  k_scale=None, v_scale=None):
    """One online-softmax step over a K/V block, all kv heads at once.

    q [KVH, G, D]; k/v [KVH, T, D] in storage dtype (upcast here);
    ``pos`` the slot's absolute query position, ``first_col`` the
    absolute position of the block's first key. ``k_scale``/``v_scale``
    [KVH, T] dequantize an int8 block: they scale the score and
    probability COLUMNS (keys ride the lane axis of both), which equals
    scaling K/V rows without relayouting the scales onto sublanes."""
    q = q.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale          # [KVH, G, T]
    if k_scale is not None:
        s = s * k_scale[:, None, :]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos >= first_col + cols, s, MASK_VALUE)

    m_prev = m_ref[:, :, :1]                                 # [KVH, G, 1]
    l_prev = l_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                   # [KVH, G, T]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale[:, None, :]
    pv = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # [KVH, G, D]
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _finalize(o_ref, acc_ref, l_ref):
    l = l_ref[:, :, :1]
    # Position 0 is always live, so l > 0 for every real slot; guard
    # anyway so padded grid rows emit zeros rather than NaN.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _scratch(hkv: int, group: int, d: int):
    return [pltpu.VMEM((hkv, group, d), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32)]


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, block_k, num_k_blocks):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    # The query sits at absolute position `pos`; cache entries at
    # [0..pos] are live. Blocks strictly past it contribute nothing.
    pos = pos_ref[pl.program_id(0)]

    @pl.when(ik * block_k <= pos)
    def _body():
        # The dense cache keeps heads BEHIND the sequence axis; the
        # contractions want them leading.
        _attend_block(q_ref[0], jnp.swapaxes(k_ref[0], 0, 1),
                      jnp.swapaxes(v_ref[0], 0, 1), pos, ik * block_k,
                      acc_ref, m_ref, l_ref, scale=scale)

    @pl.when(ik == num_k_blocks - 1)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _decode_fused(q, cache_k, cache_v, positions, *, scale, block_k,
                  interpret):
    b, hq, d = q.shape
    s_max, hkv = cache_k.shape[1], cache_k.shape[2]
    group = hq // hkv
    nk = pl.cdiv(s_max, block_k)

    qg = q.reshape(b, hkv, group, d)
    q_spec = pl.BlockSpec((1, hkv, group, d), lambda b_, j, po: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, hkv, d),
                           lambda b_, j, po: (b_, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=_scratch(hkv, group, d),
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, num_k_blocks=nk)
    itemsize = jnp.dtype(cache_k.dtype).itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
        name="decode_attn",
        cost_estimate=pl.CostEstimate(
            # One query row per slot: 2 matmuls over the live prefix.
            flops=4 * b * hq * s_max * d,
            bytes_accessed=(cache_k.size + cache_v.size) * itemsize
            + q.size * jnp.dtype(q.dtype).itemsize,
            transcendentals=b * hq * s_max,
        ),
    )(positions.astype(jnp.int32), qg, cache_k, cache_v)
    return out.reshape(b, hq, d)


def decode_applicable(s_max: int, d: int, hq: int, hkv: int, *,
                      block_k: int = 512) -> bool:
    """True when :func:`decode_attention` auto-dispatch takes the fused
    kernel for these shapes on TPU (vs the XLA reference). Kept next to
    the kernel so diagnostics (bench_serve.py) can't drift from the real
    dispatch predicate."""
    return not (hq % hkv or d % 128 or s_max % min(block_k, s_max))


def decode_attention(
    q: jnp.ndarray,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    positions: jnp.ndarray,
    scale: Optional[float] = None,
    *,
    block_k: int = 512,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode-step attention. q [B, Hq, D]; cache [B, S_max, Hkv, D]
    (GQA ok); positions [B] = each slot's current absolute position.

    ``use_kernel``: None = auto (fused kernel on TPU when the shapes
    tile, XLA reference elsewhere); True forces the kernel (interpret
    mode off-TPU — how tier-1 CPU tests exercise it); False forces the
    reference.
    """
    b, hq, d = q.shape
    s_max, hkv = cache_k.shape[1], cache_k.shape[2]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and decode_applicable(s_max, d, hq, hkv,
                                            block_k=block_k))
    if not use_kernel:
        return decode_attention_reference(q, cache_k, cache_v, positions,
                                          scale)
    if interpret is None:
        interpret = _interpret_default()
    return _decode_fused(q, cache_k, cache_v, positions, scale=scale,
                         block_k=min(block_k, s_max), interpret=interpret)
