"""The gated delta rule (Gated DeltaNet; Yang, Kautz & Hatamizadeh,
2024): a chunked scan for prefill that starts from a carried state, and
a one-token in-place update for the decode tick.

The recurrence of one head (``transformers`` ``modeling_qwen3_next.py``
``torch_recurrent_gated_delta_rule``), on a state ``S [Dk, Dv]`` (key x
value), float32, with a scalar log-decay ``g <= 0`` and a write strength
``beta`` in (0, 1) a head a token::

    S = exp(g_t) S
    u = (v_t - k_t^T S) * beta_t          what the state does NOT yet say of v_t
    S = S + k_t u^T
    o_t = q_t^T S

``q`` and ``k`` arrive L2-normalised (``q`` times ``Dk ** -0.5``): the
conv, the gates and the norm around this are the mixer's
(``models/gated_delta.py``). Unlike Mamba-2's update (``ops/ssm.py``),
the write READS the decayed state first, so the tick's kernel cannot be
``ssm_step``: a head's tile is decayed, contracted with ``k``, updated
and contracted with ``q`` inside one visit (one HBM read, one write).

:func:`gdn_chunked_scan` (prefill) is the chunkwise form in
``jax.numpy`` (``torch_chunk_gated_delta_rule``): inside a chunk of ``Q``
positions the ``u`` of every position come out of ONE unit-lower-
triangular solve (the WY representation), the outputs are masked
``[Q, Q]`` products, and the state crosses chunks by a ``lax.scan``
whose carry starts at ``state``: an engine chunk that is not a prompt's
first starts from what the one before it left. ``g = 0`` and ``beta =
0`` at a position make it the identity, which is how a right-padded row
keeps the state of its last real token.

:func:`gdn_step` (the tick) updates EVERY slot's state by one token. The
state cache ``[L_lin, slots, H, Dk, Dv]`` float32 is 2 MB a slot a layer
at Qwen3-Next's widths, read and written once a tick: pure HBM
bandwidth. The kernel (``name="gdn_step"``) takes the WHOLE array with
the layer as a scalar-prefetch operand and is aliased in -> out, as
``ssm_step`` and ``paged_kv_write`` are. Dispatch as in ``ops/ssm.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.dispatch import interpret_default

F32 = jnp.float32
LANES = 128


# ---------------------------------------------------------------------------
# Prefill: chunked scan from a carried state
# ---------------------------------------------------------------------------

_DIAGONAL = 16


def _solve_unit_lower(system, rhs):
    """``X`` with ``system X = rhs`` for a unit lower-triangular
    ``system [..., Q, Q]`` (float32): its inverse by forward substitution
    inside diagonal blocks of 16 rows (15 steps, every block at once),
    blocks merged pairwise (``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C
    A^-1, D^-1]]``) up to ``Q``, then one product with ``rhs``: as the
    published chunk kernels do it, and as stable as substitution (a
    Neumann product of powers of the strict part cancels catastrophically
    when the keys of a chunk are alike). Products at precision
    ``highest``: operands stay float32."""
    qn = system.shape[-1]
    lead = system.shape[:-2]
    hi = jax.lax.Precision.HIGHEST
    size = min(_DIAGONAL, qn)
    nb = 1 << (-(-qn // size) - 1).bit_length()     # blocks, a power of two
    if nb * size > qn:      # identity rows below: they solve to zeros
        pad = nb * size - qn
        system = jnp.pad(system, [(0, 0)] * len(lead) + [(0, pad)] * 2)
        system = system + jnp.diag(
            (jnp.arange(nb * size) >= qn).astype(system.dtype))
        return _solve_unit_lower(system, jnp.pad(
            rhs, [(0, 0)] * len(lead) + [(0, pad), (0, 0)]))[..., :qn, :]
    blocks = system.reshape(*lead, nb, size, nb, size)
    # [..., nb, size, size]: the diagonal blocks, minus their strict part.
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    neg = jnp.where(jnp.tril(jnp.ones((size, size), bool), -1), -diag, 0.0)
    rows = [neg[..., 0, :]]
    for i in range(1, size):
        row = neg[..., i, :]
        done = jnp.stack(rows, axis=-2)                     # [..., i, size]
        rows.append(row + jnp.sum(row[..., :i, None] * done, axis=-2))
    inv = jnp.stack(rows, axis=-2) + jnp.eye(size, dtype=system.dtype)
    while nb > 1:                   # merge neighbours: size -> 2 size
        blocks = system.reshape(*lead, nb // 2, 2, size, nb // 2, 2, size)
        corner = jnp.stack([blocks[..., i, 1, :, i, 0, :]
                            for i in range(nb // 2)], axis=-3)
        pairs = inv.reshape(*lead, nb // 2, 2, size, size)
        top, bottom = pairs[..., 0, :, :], pairs[..., 1, :, :]
        low = -jnp.matmul(jnp.matmul(bottom, corner, precision=hi), top,
                          precision=hi)
        inv = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
            jnp.concatenate([low, bottom], axis=-1)], axis=-2)
        nb, size = nb // 2, 2 * size
    return jnp.matmul(inv[..., 0, :, :], rhs, precision=hi)


def gdn_chunked_scan(q, k, v, g, beta, state=None, *, chunk: int = 64,
                     dtype=F32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over whole sequences. q, k [B, S, H, Dk]; v
    [B, S, H, Dv]; g, beta [B, S, H] float32 (``g = beta = 0``: the
    position is skipped); ``state`` [B, H, Dk, Dv] float32, zeros when
    None. Returns (o [B, S, H, Dv] float32, final state).

    The large products take operands in ``dtype`` (the model's dtype:
    bf16 rounds them as the published CUDA kernels do) and accumulate in
    float32; decays, cumulative sums, the triangular solve and the
    carried state stay float32."""
    bsz, s, h, dk = k.shape
    dv = v.shape[-1]
    qn = min(chunk, s)
    if s % qn:
        raise ValueError(f"sequence {s} is not a multiple of chunk {qn}")
    nc = s // qn
    if state is None:
        state = jnp.zeros((bsz, h, dk, dv), F32)

    def mm(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=F32)

    def chunks(a):          # [B, S, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(a.reshape(bsz, nc, qn, *a.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((qn, qn), bool))
    strict = jnp.tril(jnp.ones((qn, qn), bool), -1)
    eye = jnp.eye(qn, dtype=F32)

    # Everything of a chunk inside the scan's step: hoisting what does
    # not need the carry (the solve, the masked products) out of the scan
    # for all chunks at once was SLOWER on the v5e at 8 rows x 1024 (13.3
    # against 7.4 ms a layer, PR 38): its [B, nc, H, Q, ...] float32
    # intermediates cross HBM.
    def step(carry, inputs):
        qc, kc, vc, gc, bc = inputs                 # one chunk
        bc = bc.astype(F32)[..., None]
        cum = jnp.cumsum(gc.astype(F32), axis=1)    # [B, Q, H]
        cum_h = jnp.moveaxis(cum, 1, 2)             # [B, H, Q]
        # Position i sees position j <= i through exp(cum_i - cum_j).
        seg = cum_h[..., :, None] - cum_h[..., None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
        kb = kc.astype(F32) * bc
        # u_i = beta_i (v_i - k_i^T S_{i-1}) with S_{i-1} = decayed carry
        # + sum_{j<i} decayed k_j u_j^T: (I + L) U = rhs, L strictly
        # lower, solved for the carry's part and the values' part at once.
        sys = eye + jnp.where(
            strict, mm("bqhd,bkhd->bhqk", kb, kc) * decay, 0.0)
        rhs = jnp.concatenate(
            [jnp.moveaxis(vc.astype(F32) * bc, 1, 2),
             jnp.moveaxis(kb * jnp.exp(cum)[..., None], 1, 2)], axis=-1)
        solved = _solve_unit_lower(sys, rhs)        # [B, H, Q, Dv + Dk]
        u = solved[..., :dv] - mm("bhqd,bhdv->bhqv", solved[..., dv:], carry)
        attn = jnp.where(lower, mm("bqhd,bkhd->bhqk", qc, kc) * decay, 0.0)
        # Both terms head-major, moved once (XLA's CPU dot has no bf16
        # "bqhd,bhdv->bqhv").
        o = jnp.moveaxis(
            mm("bqhd,bhdv->bhqv", qc.astype(F32) * jnp.exp(cum)[..., None],
               carry)
            + mm("bhqk,bhkv->bhqv", attn, u), 1, 2)
        # What each position's write is worth at the chunk's last position.
        left = jnp.exp(cum[:, -1:, :] - cum)        # [B, Q, H]
        carry = (carry * jnp.exp(cum[:, -1])[..., None, None]
                 + mm("bqhd,bhqv->bhdv", kc.astype(F32) * left[..., None], u))
        return carry, o

    state, os = jax.lax.scan(
        step, state.astype(F32),
        (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)))
    return jnp.moveaxis(os, 0, 1).reshape(bsz, s, h, dv), state


# ---------------------------------------------------------------------------
# Tick: one token for every slot, in place
# ---------------------------------------------------------------------------

def gdn_step_reference(state, q, k, v, g, beta):
    """One step of the recurrence in float32 ``jax.numpy``: state
    [B, H, Dk, Dv]; q, k [B, H, Dk]; v [B, H, Dv]; g, beta [B, H].
    Returns (o [B, H, Dv], new state). Products and the sums over Dk are
    elementwise, so no matmul precision rounds them."""
    k = k.astype(F32)[..., None]
    new = state * jnp.exp(g.astype(F32))[..., None, None]
    u = ((v.astype(F32) - jnp.sum(new * k, axis=-2))
         * beta.astype(F32)[..., None])
    new = new + k * u[..., None, :]
    return jnp.sum(new * q.astype(F32)[..., None], axis=-2), new


def _head_block(heads: int) -> int:
    """Heads a grid step updates: one [Hb, 128, 128] float32 block in
    and one out, double-buffered, stays at 4 MiB."""
    hb = min(heads, 16)
    while heads % hb:
        hb -= 1
    return hb


def gdn_applicable(heads: int, dk: int, dv: int) -> bool:
    """True when auto-dispatch takes the ``gdn_step`` kernel on the TPU:
    a head's state of whole (8, 128) tiles and whole sublane tiles of
    heads a grid step."""
    return dv % LANES == 0 and dk % 8 == 0 and _head_block(heads) % 8 == 0


def _gdn_step_kernel(layer_ref, qt_ref, kt_ref, v_ref, decay_ref, beta_ref,
                     st_ref, o_ref, out_ref, *, heads: int):
    """``heads`` heads of one slot, each a ``[Dk, Dv]`` tile, key dim
    down the sublanes: ``k`` and ``q`` arrive as COLUMNS (``[Dk, Hb]``,
    a head a lane) and broadcast along the lanes; the sums over Dk run
    down the sublanes; v, the decay and beta are rows."""
    del layer_ref                                  # used by the index maps
    qt, kt = qt_ref[0, 0], kt_ref[0, 0]            # [Dk, Hb]
    for h in range(heads):                         # static
        k_col, q_col = kt[:, h:h + 1], qt[:, h:h + 1]
        new = st_ref[0, 0, h] * decay_ref[0, h:h + 1, :]
        u = ((v_ref[0, h:h + 1, :]
              - jnp.sum(new * k_col, axis=0, keepdims=True))
             * beta_ref[0, h:h + 1, :])
        new = new + k_col * u
        out_ref[0, 0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(new * q_col, axis=0, keepdims=True)


def _gdn_step_fused(state_all, layer, q, k, v, g, beta, *, interpret):
    bsz, h, dk = k.shape
    dv = v.shape[-1]
    hb = _head_block(h)

    def columns(a):          # [B, H, Dk] -> [B, H / Hb, Dk, Hb]
        return jnp.swapaxes(a.astype(F32).reshape(bsz, h // hb, hb, dk), 2, 3)

    def rows(a):             # [B, H] -> [B, H, Dv], the scalar on every lane
        return jnp.broadcast_to(a.astype(F32)[..., None], (bsz, h, dv))

    per_head = pl.BlockSpec((1, hb, dv), lambda i, j, ly: (i, j, 0))
    cols = pl.BlockSpec((1, 1, dk, hb), lambda i, j, ly: (i, j, 0, 0))
    state_spec = pl.BlockSpec((1, 1, hb, dk, dv),
                              lambda i, j, ly: (ly[0], i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h // hb),
        in_specs=[cols, cols, per_head, per_head, per_head, state_spec],
        out_specs=[per_head, state_spec],
    )
    state_bytes = 4 * bsz * h * dk * dv
    o, state_all = pl.pallas_call(
        functools.partial(_gdn_step_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, h, dv), F32),
                   jax.ShapeDtypeStruct(state_all.shape, state_all.dtype)],
        # Operand 6 counts the scalar-prefetch layer, q, k, v, decay, beta.
        input_output_aliases={6: 1},
        interpret=interpret,
        name="gdn_step",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=7 * bsz * h * dk * dv, transcendentals=0,
            bytes_accessed=2 * state_bytes + 4 * bsz * h * (2 * dk + 4 * dv)),
    )(jnp.asarray(layer, jnp.int32).reshape(1), columns(q), columns(k),
      v.astype(F32), rows(jnp.exp(g.astype(F32))), rows(beta), state_all)
    return o, state_all


def gdn_step(state_all, layer, q, k, v, g, beta, *,
             use_kernel: Optional[bool] = None):
    """Advance every slot's state of layer ``layer`` (a traced int32
    scalar) by one token. ``state_all`` [L_lin, B, H, Dk, Dv] float32 is
    the whole state cache; q, k [B, H, Dk]; v [B, H, Dv]; g, beta [B, H]
    float32. Returns (o [B, H, Dv] float32, the updated cache). With the
    kernel the cache is updated in place and no slab of it exists;
    without, the layer's slab is sliced out, updated and put back."""
    _, h, dk = k.shape
    tiles = gdn_applicable(h, dk, v.shape[-1])
    interpret = interpret_default()
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and tiles
    if use_kernel and (interpret or tiles):
        return _gdn_step_fused(state_all, layer, q, k, v, g, beta,
                               interpret=interpret)
    slab = jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False)
    o, new = gdn_step_reference(slab, q, k, v, g, beta)
    return o, jax.lax.dynamic_update_index_in_dim(state_all, new, layer, 0)
