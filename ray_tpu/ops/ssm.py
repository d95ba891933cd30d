"""Mamba-2 state-space recurrence: a chunked scan for prefill and a
one-token in-place update for the decode tick.

The recurrence of one head (``transformers``
``GraniteMoeHybridMambaLayer.torch_forward``; Dao & Gu, "Transformers
are SSMs", 2024), with a scalar decay a head::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T      h [P, N]
    y_t = h_t C_t                                          y [P]

``x [P]`` is the head's slice of the convolved input, ``B``/``C [N]``
are shared by the heads of a group, ``dt > 0`` and ``A < 0``. The skip
term ``D * x``, the gate and the norm are the mixer's
(``models/mamba2.py``), not this module's.

:func:`ssm_chunked_scan` (prefill) is the SSD form in ``jax.numpy``:
inside a chunk of ``Q`` positions the outputs are one masked
``[Q, Q]`` product, and the state crosses chunks by a ``lax.scan``, so
the work is matrix multiplications and only ``S / Q`` steps are
sequential. The chunk length changes rounding, not the result: the
published ``mamba_chunk_size`` is a kernel parameter. ``dt = 0`` at a
position makes it the identity (decay 1, nothing added), which is how a
right-padded row keeps the state of its last real token.

:func:`ssm_step` (the tick) updates EVERY slot's state by one token.
The state cache ``[L_ssm, slots, H, P, N]`` float32 is 4 MB a slot a
layer at Granite 4.0-H's widths, read and written once a tick: pure
HBM bandwidth. The kernel (``name="ssm_step"``) takes the WHOLE array
with the layer as a scalar-prefetch operand and is aliased in -> out,
as ``paged_kv_write`` is for the K/V arena: an XLA update inside the
layer loop would slice the layer's slab out and put it back, two more
passes over it.

Dispatch as in ``ops/moe.py``: ``use_kernel`` None = the kernel on the
TPU when the shapes tile, ``jax.numpy`` elsewhere; True forces the
kernel (interpreted off the TPU: the CPU tier-1 path).
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


def _per_head(v, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: a group's B or C for each of
    its ``H / G`` heads."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# Prefill: chunked scan
# ---------------------------------------------------------------------------

def ssm_chunked_scan(x, dt, a, b, c, state=None, *, chunk: int = 128,
                     dtype=F32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over whole sequences. x [B, S, H, P]; dt [B, S, H]
    float32 (0 = the position is skipped); a [H] (negative); b, c
    [B, S, G, N]; ``state`` [B, H, P, N] float32, zeros when None.
    Returns (y [B, S, H, P] float32, final state [B, H, P, N] float32).

    The four large products take operands in ``dtype`` (the model's
    dtype: bf16 rounds them as the published CUDA kernels do) and
    accumulate in float32; decays, cumulative sums and the carried state
    stay float32."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"sequence {s} is not a multiple of chunk {q}")
    nc = s // q
    if state is None:
        state = jnp.zeros((bsz, h, p, n), F32)

    def mm(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          preferred_element_type=F32)

    def chunks(v):          # [B, S, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(v.reshape(bsz, nc, q, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def step(carry, inputs):
        xc, dtc, bc, cc = inputs                    # one chunk
        dtc = dtc.astype(F32)
        xdt = xc.astype(F32) * dtc[..., None]       # [B, Q, H, P]
        cum = jnp.cumsum(dtc * a.astype(F32), axis=1)      # [B, Q, H]
        cum_h = jnp.moveaxis(cum, 1, 2)             # [B, H, Q]
        # Position i sees position j <= i through exp(cum_i - cum_j).
        seg = cum_h[..., :, None] - cum_h[..., None, :]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        cb = mm("bqgn,bkgn->bgqk", cc, bc)          # [B, G, Q, Q]
        y = mm("bhqk,bkhp->bqhp",
               jnp.repeat(cb, h // cb.shape[1], axis=1) * decay, xdt)
        # What the carried state adds: C_i h_in, decayed to position i.
        ch = _per_head(cc, h)                       # [B, Q, H, N]
        y = y + (mm("bqhn,bhpn->bqhp", ch, carry)
                 * jnp.exp(cum)[..., None])
        # The chunk's own contribution to the state at its last position.
        tail = jnp.exp(cum[:, -1:, :] - cum)        # [B, Q, H]
        grown = mm("bqhp,bqhn->bhpn", xdt * tail[..., None],
                   _per_head(bc, h))
        carry = carry * jnp.exp(cum[:, -1])[..., None, None] + grown
        return carry, y

    state, ys = jax.lax.scan(step, state.astype(F32),
                             (chunks(x), chunks(dt), chunks(b), chunks(c)))
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p), state


# ---------------------------------------------------------------------------
# Tick: one token for every slot, in place
# ---------------------------------------------------------------------------

LANES = 128


def _fold(p: int, n: int) -> int:
    """State rows that share one 128-lane row of the cache (below)."""
    f = max(LANES // p, 1)
    return f if n % f == 0 else 1


def packed_shape(heads: int, p: int, n: int) -> Tuple[int, int, int]:
    """A slot's state as the cache keeps it: ``[H, N / f, f * P]``, the
    head's ``[P, N]`` state TRANSPOSED to N-major and ``f = 128 / P``
    consecutive N-rows laid side by side so a row fills the 128 lanes
    (no padding in HBM at P = 64). In this layout the tick's sum over N
    runs down sublanes (vector adds, not a lane reduction a row), x
    broadcasts along sublanes, and B and C, which every head of a slot
    shares, are the only operands that must be turned: once a slot."""
    f = _fold(p, n)
    return heads, n // f, f * p


def pack_state(state):
    """``[..., H, P, N]`` -> the cache's ``[..., H, N / f, f * P]``."""
    *lead, h, p, n = state.shape
    return jnp.swapaxes(state, -1, -2).reshape(*lead, *packed_shape(h, p, n))


def unpack_state(packed, p: int):
    """The inverse of :func:`pack_state` for head size ``p``."""
    *lead, h, rows, width = packed.shape
    n = rows * width // p
    return jnp.swapaxes(packed.reshape(*lead, h, n, p), -1, -2)


def ssm_step_reference(state, x, dt, a, b, c):
    """One step of the recurrence in float32 ``jax.numpy``: state
    [B, H, P, N]; x [B, H, P]; dt [B, H]; a [H]; b, c [B, G, N].
    Returns (y [B, H, P], new state). Products and the sum over N are
    elementwise, so no matmul precision rounds them."""
    h = state.shape[1]
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))[..., None, None]
    xdt = (x.astype(F32) * dt[..., None])[..., None]
    new = (state * decay
           + xdt * _per_head(b.astype(F32), h)[:, :, None, :])
    y = jnp.sum(new * _per_head(c.astype(F32), h)[:, :, None, :], axis=-1)
    return y, new


def _head_block(heads: int) -> int:
    """Heads a grid step updates: one [Hb, 64, 128] float32 block in
    and one out, double-buffered, stays at 4 MiB."""
    hb = min(heads, 32)
    while heads % hb:
        hb -= 1
    return hb


def ssm_applicable(heads: int, p: int, n: int, groups: int) -> bool:
    """True when auto-dispatch takes the ``ssm_step`` kernel on the
    TPU: packed rows of whole 128-lane tiles, whole sublane tiles of
    heads, and one group (a step's B and C are then one row each)."""
    _, rows, width = packed_shape(heads, p, n)
    return (groups == 1 and width % LANES == 0 and rows % 8 == 0
            and _head_block(heads) % 8 == 0)


def _ssm_step_kernel(layer_ref, xdt_ref, decay_ref, b_ref, c_ref, st_ref,
                     y_ref, out_ref, *, heads: int):
    """``heads`` heads of one slot, each a packed ``[N / f, f * P]``
    tile: ``new = state * decay + B (x) (dt x)``, ``y = sum_N new * C``
    (still folded ``f`` ways along the lanes)."""
    del layer_ref                                  # used by the index maps
    b_tile, c_tile = b_ref[0], c_ref[0]            # [N / f, f * P]
    for h in range(heads):                         # static
        new = (st_ref[0, 0, h] * decay_ref[0, h:h + 1, :]
               + b_tile * xdt_ref[0, h:h + 1, :])
        out_ref[0, 0, h] = new
        y_ref[0, h:h + 1, :] = jnp.sum(new * c_tile, axis=0, keepdims=True)


def _ssm_step_fused(state_all, layer, x, dt, a, b, c, *, interpret):
    bsz, h, p = x.shape
    _, _, _, rows, width = state_all.shape
    f = width // p
    hb = _head_block(h)
    dt = dt.astype(F32)

    def head_rows(v):        # [B, H, P] -> [B, H, f * P]: lane l holds p = l % P
        return jnp.tile(v, (1, 1, f))

    def n_tiles(v):          # [B, 1, N] -> [B, N / f, f * P]: row r, lane l
        v = v.astype(F32).reshape(bsz, rows, f, 1)     # holds n = r * f + l // P
        return jnp.broadcast_to(v, (bsz, rows, f, p)).reshape(bsz, rows, width)

    per_head = pl.BlockSpec((1, hb, width), lambda i, j, ly: (i, j, 0))
    per_slot = pl.BlockSpec((1, rows, width), lambda i, j, ly: (i, 0, 0))
    state_spec = pl.BlockSpec((1, 1, hb, rows, width),
                              lambda i, j, ly: (ly[0], i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h // hb),
        in_specs=[per_head, per_head, per_slot, per_slot, state_spec],
        out_specs=[per_head, state_spec],
    )
    state_bytes = 4 * bsz * h * rows * width
    y, state_all = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, h, width), F32),
                   jax.ShapeDtypeStruct(state_all.shape, state_all.dtype)],
        # Operand 5 counts the scalar-prefetch layer, x dt, decay, B and C.
        input_output_aliases={5: 1},
        interpret=interpret,
        name="ssm_step",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=5 * bsz * h * rows * width, transcendentals=0,
            bytes_accessed=2 * state_bytes
            + 4 * bsz * width * (3 * h + 2 * rows)),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      head_rows(x.astype(F32) * dt[..., None]),
      head_rows(jnp.broadcast_to(jnp.exp(dt * a.astype(F32))[..., None],
                                 (bsz, h, p))),
      n_tiles(b), n_tiles(c), state_all)
    return y.reshape(bsz, h, f, p).sum(axis=2), state_all


def ssm_step(state_all, layer, x, dt, a, b, c, *,
             use_kernel: Optional[bool] = None):
    """Advance every slot's state of layer ``layer`` (a traced int32
    scalar) by one token. ``state_all`` [L_ssm, B, H, N / f, f * P]
    float32 is the whole state cache (:func:`packed_shape`); x
    [B, H, P]; dt [B, H] float32; a [H]; b, c [B, G, N]. Returns
    (y [B, H, P] float32, the updated cache). With the kernel the cache
    is updated in place and no slab of it exists; without, the layer's
    slab is sliced out, updated and put back."""
    bsz, h, p = x.shape
    n = b.shape[-1]
    groups = b.shape[1]
    tiles = ssm_applicable(h, p, n, groups)
    interpret = interpret_default()
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and tiles
    if use_kernel and groups == 1 and (interpret or tiles):
        return _ssm_step_fused(state_all, layer, x, dt, a, b, c,
                               interpret=interpret)
    slab = jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False)
    y, new = ssm_step_reference(unpack_state(slab, p), x, dt, a, b, c)
    return y, jax.lax.dynamic_update_index_in_dim(
        state_all, pack_state(new), layer, 0)


# ---------------------------------------------------------------------------
# The depthwise causal convolution in front of the recurrence
# ---------------------------------------------------------------------------

def causal_conv(x, w, bias):
    """Depthwise causal convolution from an empty history: x [B, S, C];
    w [K, C] (tap ``K - 1`` multiplies the current position); bias [C].
    ``out[t] = bias + sum_k w[k] * x[t - (K - 1) + k]``, float32."""
    k = w.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(F32)
    for j in range(k):
        out = out + padded[:, j:j + s] * w[j].astype(F32)
    return out


def conv_tail(x, lengths, k: int):
    """The ``k - 1`` inputs before position ``lengths`` of each row:
    x [B, S, C], lengths [B] -> [B, k - 1, C], zeros where the row is
    shorter. What a convolution needs to go on from ``lengths``."""
    idx = lengths[:, None] - (k - 1) + jnp.arange(k - 1)[None, :]
    got = jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1)
    return jnp.where((idx >= 0)[..., None], got, 0).astype(x.dtype)


def conv_step(tail, new, w, bias):
    """One position of :func:`causal_conv` on a carried history: tail
    [B, K - 1, C] (oldest first), new [B, C]. Returns (out [B, C]
    float32, the next tail)."""
    window = jnp.concatenate([tail, new[:, None].astype(tail.dtype)], axis=1)
    out = bias.astype(F32) + jnp.sum(
        window.astype(F32) * w.astype(F32)[None], axis=1)
    return out, window[:, 1:]
