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

:func:`gdn_chunked_scan` (prefill) is the chunkwise form
(``torch_chunk_gated_delta_rule``): inside a chunk of ``Q`` positions
the ``u`` of every position come out of ONE unit-lower-triangular solve
(the WY representation), the outputs are masked ``[Q, Q]`` products, and
the state crosses chunks from a carry that starts at ``state``: an
engine chunk that is not a prompt's first starts from what the one
before it left. ``g = 0`` and ``beta = 0`` at a position make it the
identity, which is how a right-padded row keeps the state of its last
real token. It has two bodies with the same rounding points (the large
products take operands in the model's dtype and accumulate in float32;
decays, the solve and the carry are float32):

* the kernel (``name="gdn_chunk_scan"``, PR 44), taken on the TPU where
  :func:`gdn_scan_applicable` holds: grid (rows, blocks of 4 value
  heads, chunks), the chunk axis sequential; a head's state stays in
  VMEM for the row's chunks (the output block, loaded at chunk 0 and
  written back after the last); q and k are read by KEY head, a head a
  lane-aligned slice of the ``[S, Hk Dk]`` rows, so no repeated or
  transposed copy of q, k, v or o exists; the solve is forward
  substitution inside 16-row diagonal blocks on the vector unit and one
  float32 product at precision ``highest`` for what finished rows owe
  the block below (:func:`_solve_in_place`). On the v5e it is bound by
  the vector unit's issue rate, about 0.50 us a head a chunk at every
  row count, against 1.3-1.6 (and 0.25 of copies around it) for
* the ``jax.numpy`` body under ``lax.scan``: the CPU's path, the path of
  heads narrower than a lane tile, and the kernel's reference.

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
                     dtype=F32, use_kernel: Optional[bool] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over whole sequences. q, k [B, S, Hk, Dk] with
    ``Hk`` dividing ``H`` (value head ``h`` reads key head ``h // (H /
    Hk)``); v [B, S, H, Dv]; g, beta [B, S, H] float32 (``g = beta =
    0``: the position is skipped); ``state`` [B, H, Dk, Dv] float32,
    zeros when None. Returns (o [B, S, H, Dv] float32, final state).

    The large products take operands in ``dtype`` (the model's dtype:
    bf16 rounds them as the published CUDA kernels do) and accumulate in
    float32; decays, cumulative sums, the triangular solve and the
    carried state stay float32.

    ``use_kernel`` None takes the ``gdn_chunk_scan`` kernel on the TPU
    where :func:`gdn_scan_applicable` holds, and the ``jax.numpy`` body
    below everywhere else (the CPU, heads narrower than a lane tile):
    the kernel's reference and the one fallback."""
    bsz, s, hk, dk = k.shape
    h, dv = v.shape[-2:]
    qn = min(chunk, s)
    if s % qn:
        raise ValueError(f"sequence {s} is not a multiple of chunk {qn}")
    nc = s // qn
    tiles = gdn_scan_applicable(h, hk, dk, dv, qn)
    interpret = interpret_default()
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and tiles
    if use_kernel and tiles:
        return _gdn_chunk_scan_fused(q, k, v, g, beta, state, chunk=qn,
                                     dtype=dtype, interpret=interpret)
    if hk != h:
        q, k = (jnp.repeat(a, h // hk, axis=2) for a in (q, k))
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
# Prefill: the same chunks as one kernel
# ---------------------------------------------------------------------------

_SCAN_HEADS = 4
_SUBLANES = 8


def _scan_head_block(heads: int, key_heads: int) -> int:
    """Value heads a grid step of ``gdn_chunk_scan`` covers: whole key
    heads' shares, up to ``_SCAN_HEADS``. Their chains of substitution
    steps are independent, so the scheduler interleaves them, and they
    share the grid step's fixed cost; but the body is unrolled a head,
    and every process traces and lowers it anew for each prefill
    program: on the v5e 4, 8 and 16 heads a step read 0.69, 0.68 and
    0.67 us a head a chunk, and 8 cost a run twice 4's seconds of
    set-up (PR 44). 0 when no such block divides ``heads``."""
    if key_heads <= 0 or heads % key_heads:
        return 0
    share = heads // key_heads
    hb = min(heads, _SCAN_HEADS) // share * share
    while hb and heads % hb:
        hb -= share
    return hb


def gdn_scan_applicable(heads: int, key_heads: int, dk: int, dv: int,
                        chunk: int) -> bool:
    """True when auto-dispatch takes the ``gdn_chunk_scan`` kernel on
    the TPU: key and value dims of whole lane tiles (a head is a lane-
    aligned slice of the ``[S, H D]`` rows), a chunk of whole diagonal
    blocks, and whole blocks of value heads over whole key heads."""
    return (dk % LANES == 0 and dv % LANES == 0 and chunk % _DIAGONAL == 0
            and _scan_head_block(heads, key_heads) > 0)


def _row_tiles(a):
    """``[Q, N]`` as its ``Q / 8`` sublane tiles ``[8, N]``."""
    return [a[t:t + _SUBLANES] for t in range(0, a.shape[0], _SUBLANES)]


def _solve_in_place(systems, sides):
    """``(I + L)^-1 rhs`` for every head of a grid step, in place on
    lists of row tiles: ``systems[h]`` the tiles of the strictly lower
    ``L [Q, Q]``, ``sides[h]`` the tiles of the right-hand sides ``[Q,
    N]``. The published kernels' shape, on this chip's units: diagonal
    blocks of 16 rows by forward substitution on the vector unit (column
    by column: once row ``j`` is final, ``L[:, j]`` times it leaves
    every later row of the block; float32, no matmul rounds it and no
    power of ``L`` is formed), and what the finished rows owe a block
    below them as ONE float32 product at precision ``highest`` (six
    bf16 passes in Mosaic as in XLA; at the default it is ONE, which
    the interpreter does not show: ``chip_smoke.py --phases linear``
    holds this solve to a float64 one on the chip). The heads' chains
    are independent and written interleaved, a column of every head
    before the next column."""
    qn = systems[0][0].shape[-1]
    per = _DIAGONAL // _SUBLANES
    for lo in range(0, qn, _DIAGONAL):
        first = lo // _SUBLANES
        if lo:
            for ls, xs in zip(systems, sides):
                owed = jnp.dot(
                    jnp.concatenate(ls[first:first + per], axis=0)[:, :lo],
                    jnp.concatenate(xs[:first], axis=0),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=F32)
                for t, part in enumerate(_row_tiles(owed)):
                    xs[first + t] = xs[first + t] - part
        for j in range(lo, lo + _DIAGONAL - 1):
            tj, r = divmod(j, _SUBLANES)
            for ls, xs in zip(systems, sides):
                row = xs[tj][r:r + 1]
                for t in range((j + 1) // _SUBLANES, first + per):
                    xs[t] = xs[t] - ls[t][:, j:j + 1] * row


def _gdn_chunk_scan_kernel(*refs, heads: int, share: int, dk: int, dv: int,
                           has_state: bool, dtype):
    """One chunk of ``heads`` value heads of one row. Rows of q, k, v
    and o are positions, a head a lane-aligned slice; ``cols`` [Q, 2 Hb]
    holds beta and the chunk's cumulative log-decay a head a lane,
    ``rows`` [Hb, Q] the cumulative log-decay a head a sublane,
    ``whole`` the chunk's whole decay a head, scalars in SMEM (Mosaic
    does not broadcast a ``[1, 1]`` of ``cols`` over a ``[Dk, Dv]``
    tile). The heads' states live in ``out_ref``, whose block index
    does not move along the chunk axis: loaded at chunk 0, written back
    after the last. Every product rounds what
    :func:`gdn_chunked_scan`'s ``mm`` rounds."""
    whole_ref, q_ref, k_ref, v_ref, cols_ref, rows_ref = refs[:6]
    st_ref, o_ref, out_ref = refs[6:] if has_state else (None, *refs[6:])

    @pl.when(pl.program_id(2) == 0)
    def _load():
        out_ref[...] = (st_ref[...] if has_state
                        else jnp.zeros(out_ref.shape, F32))

    qn = q_ref.shape[1]
    # [B, nc, H] flat: this row, this chunk, this block's first head.
    first = ((pl.program_id(0) * pl.num_programs(2) + pl.program_id(2))
             * pl.num_programs(1) + pl.program_id(1)) * heads

    def mm(lhs, rhs, contract):
        return jax.lax.dot_general(
            lhs.astype(dtype), rhs.astype(dtype), (contract, ((), ())),
            preferred_element_type=F32)

    nt, nn, tn = ((1,), (1,)), ((1,), (0,)), ((0,), (0,))
    cols, cum_rows = cols_ref[0, 0, 0], rows_ref[0, 0, 0]
    cum = cols[:, heads:]                               # [Q, Hb]
    grown = jnp.exp(cum)
    left = jnp.exp(cum[qn - 1:] - cum)
    at = jax.lax.broadcasted_iota(jnp.int32, (qn, qn), 0)
    seen = jax.lax.broadcasted_iota(jnp.int32, (qn, qn), 1)
    lower, strict = at >= seen, at > seen

    systems, sides, kept = [], [], []
    for key in range(heads // share):
        qf = q_ref[0, :, key * dk:(key + 1) * dk]       # [Q, Dk] float32
        kf = k_ref[0, :, key * dk:(key + 1) * dk]
        kr = kf.astype(dtype)
        scores = mm(qf, kr, nt)                         # [Q, Q]
        for h in range(key * share, (key + 1) * share):
            b = cols[:, h:h + 1]
            seg = cum[:, h:h + 1] - cum_rows[h:h + 1]
            # Above the diagonal 1, not 0: both uses mask it themselves.
            decay = jnp.exp(jnp.where(lower, seg, 0.0))
            kb = kf * b
            systems.append(_row_tiles(
                jnp.where(strict, mm(kb, kr, nt) * decay, 0.0)))
            # The values' part and the carry's part of the solve at once.
            sides.append(_row_tiles(jnp.concatenate(
                [v_ref[0, :, h * dv:(h + 1) * dv].astype(F32) * b,
                 kb * grown[:, h:h + 1]], axis=1)))
            kept.append((h, qf, kf, jnp.where(lower, scores * decay, 0.0)))
    _solve_in_place(systems, sides)
    for (h, qf, kf, attn), solved in zip(kept, sides):
        solved = jnp.concatenate(solved, axis=0)        # [Q, Dv + Dk]
        carry = out_ref[0, h]                           # [Dk, Dv]
        cr = carry.astype(dtype)
        u = solved[:, :dv] - mm(solved[:, dv:], cr, nn)
        o_ref[0, :, h * dv:(h + 1) * dv] = (
            mm(qf * grown[:, h:h + 1], cr, nn) + mm(attn, u, nn))
        out_ref[0, h] = (carry * whole_ref[first + h]
                         + mm(kf * left[:, h:h + 1], u, tn))


# Jitted for its cache: a prefill program calls it once a run of linear
# layers, and the kernel's body (every substitution step of four heads,
# unrolled) is traced and lowered once a shape, not once a call.
@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
def _gdn_chunk_scan_fused(q, k, v, g, beta, state, *, chunk: int, dtype,
                          interpret: bool):
    bsz, s, hk, dk = k.shape
    h, dv = v.shape[-2:]
    nc, hb = s // chunk, _scan_head_block(h, hk)
    share, blocks = h // hk, h // hb
    kb = hb // share                                    # key heads a block

    def by_block(a):         # [B, S, H] -> [B, nc, H / Hb, Q, Hb]
        return jnp.moveaxis(
            a.astype(F32).reshape(bsz, nc, chunk, blocks, hb), 3, 2)

    cum = jnp.cumsum(g.astype(F32).reshape(bsz, nc, chunk, h), axis=2)
    cols = jnp.concatenate([by_block(beta), by_block(cum)], axis=-1)
    rows = jnp.swapaxes(by_block(cum), -1, -2)
    keyed = pl.BlockSpec((1, chunk, kb * dk), lambda i, j, c: (i, c, j))
    valued = pl.BlockSpec((1, chunk, hb * dv), lambda i, j, c: (i, c, j))
    held = pl.BlockSpec((1, hb, dk, dv), lambda i, j, c: (i, j, 0, 0))
    operands = [jnp.exp(cum[:, :, -1]).reshape(-1),
                q.astype(F32).reshape(bsz, s, hk * dk),
                k.astype(F32).reshape(bsz, s, hk * dk),
                v.reshape(bsz, s, h * dv), cols, rows]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), keyed, keyed, valued,
                pl.BlockSpec((1, 1, 1, chunk, 2 * hb),
                             lambda i, j, c: (i, c, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, hb, chunk),
                             lambda i, j, c: (i, c, j, 0, 0))]
    if state is not None:
        operands.append(state.astype(F32))
        in_specs.append(held)
    units = bsz * h * nc
    o, state = pl.pallas_call(
        functools.partial(_gdn_chunk_scan_kernel, heads=hb, share=share,
                          dk=dk, dv=dv, has_state=state is not None,
                          dtype=dtype),
        grid=(bsz, blocks, nc),
        in_specs=in_specs,
        out_specs=[valued, held],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, h * dv), F32),
                   jax.ShapeDtypeStruct((bsz, h, dk, dv), F32)],
        interpret=interpret,
        name="gdn_chunk_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=units * chunk * (
                2 * (2 * chunk * dk + 2 * dk * dv + chunk * dv + dk * dv)
                + chunk * (dk + dv)),
            transcendentals=units * chunk * (chunk + 3),
            bytes_accessed=(8 * bsz * s * hk * dk + 8 * bsz * s * h
                            + bsz * s * h * dv * (v.dtype.itemsize + 4)
                            + 8 * bsz * h * dk * dv)),
    )(*operands)
    return o.reshape(bsz, s, h, dv), state


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
