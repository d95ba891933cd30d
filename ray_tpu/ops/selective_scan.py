"""Mamba-1's selective scan (Gu & Dao, "Mamba: Linear-Time Sequence
Modeling with Selective State Spaces", 2023): a scan over positions for
prefill that starts from a carried state, and a one-token in-place
update for the decode tick.

The recurrence of one channel ``d`` (``transformers``
``JambaMambaMixer.slow_forward``), on a state ``s [N]`` float32, with a
decay for EVERY (channel, state) pair::

    s_t[d, n] = exp(dt_t[d] A[d, n]) s_{t-1}[d, n] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n s_t[d, n] C_t[n]

``u [D]`` is the convolved input, ``dt [D] > 0`` the time step a channel,
``A [D, N] < 0``, ``B``/``C [N]`` shared by every channel of a token.
The skip ``D u``, the gate, the convolution and the three norms are the
mixer's (``models/mamba1.py``), not this module's. Unlike Mamba-2's
(``ops/ssm.py``: one scalar decay a head), the decay is a ``[N, D]``
matrix of ``exp`` a token, so the recurrence has no matrix-product form:
six vector operations and one ``exp`` a state element a token, whatever
the path.

Everything here keeps a state ``[N, D]``: CHANNELS ON LANES (``N`` = 16
is two sublane tiles, ``D`` = 5120 forty lane tiles at Jamba2-3B's
widths), so ``dt`` and ``u`` broadcast down sublanes, the sum over ``N``
runs down sublanes, and ``B`` and ``C`` are the only operands that must
be turned to columns. ``a_t`` is ``A`` transposed, ``[N, D]``.

:func:`mamba1_scan` (prefill): the kernel (``name="mamba1_scan"``) has
grid (rows, channel blocks, time blocks), the time axis sequential; a
block's state ``[N, 512]`` lives in the output block (loaded from the
initial state at time block 0, written back after the last) and in
registers across a time block's positions, which are unrolled; ``y`` is
written a position. ``dt = 0`` at a position makes it the identity,
which is how a right-padded row keeps the state of its last real token.

:func:`mamba1_step` (the tick) advances EVERY slot's state by one
token. The state cache ``[L, slots, N, D]`` float32 is 327,680 B a slot
a layer at Jamba2-3B's widths, read and written once a tick. The kernel
(``name="mamba1_step"``) takes the WHOLE array with the layer as a
scalar-prefetch operand, aliased in -> out as ``ssm_step`` is, eight
slots a grid step, ``a_t`` resident across them.

The ``jax.numpy`` forms (``lax.scan`` over positions) are the CPU's path
and the kernels' oracle, not a fallback on the chip: a scan there sends
the ``[rows, N, D]`` carry through HBM at every position. Dispatch as
``ops/ssm.py``: ``use_kernel`` None = the kernel on the TPU when the
shapes tile; True forces it (interpreted off the TPU).
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
# Channels a kernel body holds in registers at once: a [16, 512] float32
# state is 8 vector registers.
CHANNEL_BLOCK = 512
# Positions a grid step of the scan unrolls; slots one of the step.
TIME_BLOCK = 128
SLOT_BLOCK = 8
_VMEM_LIMIT = 48 << 20


# ---------------------------------------------------------------------------
# jax.numpy forms
# ---------------------------------------------------------------------------

def mamba1_step_reference(state, u, dt, a_t, b, c):
    """One position of the recurrence, float32: state [R, N, D]; u, dt
    [R, D]; a_t [N, D]; b, c [R, N]. Returns (y [R, D], new state).
    Elementwise products and a sum over N: no matmul precision rounds
    them."""
    dt = dt.astype(F32)[:, None, :]
    new = (state * jnp.exp(dt * a_t.astype(F32))
           + b.astype(F32)[:, :, None] * (dt * u.astype(F32)[:, None, :]))
    return jnp.sum(new * c.astype(F32)[:, :, None], axis=1), new


def mamba1_scan_reference(u, dt, a_t, b, c, state=None):
    """:func:`mamba1_scan` as a ``lax.scan`` of
    :func:`mamba1_step_reference` over positions."""
    rows, _, d = u.shape
    if state is None:
        state = jnp.zeros((rows, a_t.shape[0], d), F32)

    def step(s, inputs):
        y, s = mamba1_step_reference(s, *inputs[:2], a_t, *inputs[2:])
        return s, y

    state, ys = jax.lax.scan(
        step, state.astype(F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (u, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), state


# ---------------------------------------------------------------------------
# Prefill: the scan over positions
# ---------------------------------------------------------------------------

def _channel_block(d: int) -> int:
    return CHANNEL_BLOCK if d % CHANNEL_BLOCK == 0 else d


def scan_applicable(s: int, n: int, d: int) -> bool:
    """True when auto-dispatch takes the ``mamba1_scan`` kernel on the
    TPU: whole sublane tiles of states and of a time block's positions,
    whole lane tiles of channels."""
    tb = min(s, TIME_BLOCK)
    return n % 8 == 0 and d % LANES == 0 and tb % 8 == 0 and s % tb == 0


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, init_ref, y_ref,
                 st_ref, *, steps: int):
    """``steps`` positions of one row's channel block: u, dt, y
    ``[1, steps, Db]``; a ``[N, Db]``; b, c ``[1, 1, N, steps]`` (a
    position a COLUMN); the state ``[1, N, Db]``, which is the output
    block itself across the row's time blocks."""
    @pl.when(pl.program_id(2) == 0)
    def _load():
        st_ref[...] = init_ref[...]

    a = a_ref[...]
    s = st_ref[0]
    for t in range(steps):                         # static: s in registers
        dt = dt_ref[0, t:t + 1, :]                 # [1, Db]
        s = (s * jnp.exp(dt * a)
             + b_ref[0, 0, :, t:t + 1] * (dt * u_ref[0, t:t + 1, :]))
        y_ref[0, t:t + 1, :] = jnp.sum(s * c_ref[0, 0, :, t:t + 1], axis=0,
                                       keepdims=True)
    st_ref[0] = s


def scan_cost(rows: int, s: int, n: int, d: int) -> pl.CostEstimate:
    """What :func:`mamba1_scan`'s kernel moves: six operations and one
    ``exp`` a state element a position; u, dt in and y out a position
    (float32), b and c, a state in and out a row, ``a_t`` once a row."""
    return pl.CostEstimate(
        flops=6 * rows * s * n * d, transcendentals=rows * s * n * d,
        bytes_accessed=4 * rows * (s * (3 * d + 2 * n) + 3 * n * d))


def _scan_fused(u, dt, a_t, b, c, state, *, interpret):
    rows, s, d = u.shape
    n = a_t.shape[0]
    tb, db = min(s, TIME_BLOCK), _channel_block(d)

    def columns(v):          # [R, S, N] -> [R, S / tb, N, tb]
        return jnp.swapaxes(v.astype(F32).reshape(rows, s // tb, tb, n), 2, 3)

    per_pos = pl.BlockSpec((1, tb, db), lambda r, j, k: (r, k, j))
    per_col = pl.BlockSpec((1, 1, n, tb), lambda r, j, k: (r, k, 0, 0))
    per_row = pl.BlockSpec((1, n, db), lambda r, j, k: (r, 0, j))
    return pl.pallas_call(
        functools.partial(_scan_kernel, steps=tb),
        grid=(rows, d // db, s // tb),
        in_specs=[per_pos, per_pos,
                  pl.BlockSpec((n, db), lambda r, j, k: (0, j)),
                  per_col, per_col, per_row],
        out_specs=[per_pos, per_row],
        out_shape=[jax.ShapeDtypeStruct((rows, s, d), F32),
                   jax.ShapeDtypeStruct((rows, n, d), F32)],
        interpret=interpret,
        name="mamba1_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=scan_cost(rows, s, n, d),
    )(u.astype(F32), dt.astype(F32), a_t.astype(F32), columns(b), columns(c),
      state)


def mamba1_scan(u, dt, a_t, b, c, state=None, *,
                use_kernel: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over whole rows: u [R, S, D]; dt [R, S, D] float32
    (0 = the position is skipped); a_t [N, D] (negative); b, c
    [R, S, N]; ``state`` [R, N, D] float32, zeros when None. Returns
    (y [R, S, D] float32, final state [R, N, D] float32)."""
    rows, s, d = u.shape
    n = a_t.shape[0]
    tiles = scan_applicable(s, n, d)
    interpret = interpret_default()
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and tiles
    if use_kernel and (tiles or (interpret and s % min(s, TIME_BLOCK) == 0)):
        if state is None:
            state = jnp.zeros((rows, n, d), F32)
        return tuple(_scan_fused(u, dt, a_t, b, c, state.astype(F32),
                                 interpret=interpret))
    return mamba1_scan_reference(u, dt, a_t, b, c, state)


# ---------------------------------------------------------------------------
# Tick: one token for every slot, in place
# ---------------------------------------------------------------------------

def _slot_block(slots: int) -> int:
    """Slots a grid step updates: eight ``[N, D]`` float32 states in and
    eight out, double-buffered, are 10.5 MB at Jamba2-3B's widths."""
    return SLOT_BLOCK if slots % SLOT_BLOCK == 0 else slots


def step_applicable(slots: int, n: int, d: int) -> bool:
    """True when auto-dispatch takes the ``mamba1_step`` kernel on the
    TPU: whole tiles of a state, and slots in blocks of eight (or few
    enough to be one block)."""
    return (n % 8 == 0 and d % LANES == 0
            and (slots % SLOT_BLOCK == 0 or slots < SLOT_BLOCK))


def _step_kernel(layer_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, st_ref,
                 y_ref, out_ref, *, slots: int, width: int):
    """``slots`` slots of one layer: u, dt, y ``[slots, D]``; a
    ``[N, D]``; b, c ``[1, N, slots]`` (a slot a COLUMN); the states
    ``[1, slots, N, D]``, ``width`` channels at a time."""
    del layer_ref                                  # used by the index maps
    d = a_ref.shape[1]
    for j in range(slots):                         # static
        b_col, c_col = b_ref[0, :, j:j + 1], c_ref[0, :, j:j + 1]
        for lo in range(0, d, width):
            sl = slice(lo, lo + width)
            dt = dt_ref[j:j + 1, sl]               # [1, width]
            new = (st_ref[0, j, :, sl] * jnp.exp(dt * a_ref[:, sl])
                   + b_col * (dt * u_ref[j:j + 1, sl]))
            out_ref[0, j, :, sl] = new
            y_ref[j:j + 1, sl] = jnp.sum(new * c_col, axis=0, keepdims=True)


def step_cost(slots: int, n: int, d: int) -> pl.CostEstimate:
    """What :func:`mamba1_step`'s kernel moves: six operations and one
    ``exp`` a state element; every slot's state in and out, u and dt in
    and y out (float32), b and c, ``a_t`` once."""
    return pl.CostEstimate(
        flops=6 * slots * n * d, transcendentals=slots * n * d,
        bytes_accessed=4 * (slots * (2 * n * d + 3 * d + 2 * n) + n * d))


def _step_fused(state_all, layer, u, dt, a_t, b, c, *, interpret):
    slots, d = u.shape
    n = a_t.shape[0]
    sb = _slot_block(slots)

    def columns(v):          # [B, N] -> [B / sb, N, sb]
        return jnp.swapaxes(v.astype(F32).reshape(slots // sb, sb, n), 1, 2)

    per_slot = pl.BlockSpec((sb, d), lambda i, ly: (i, 0))
    per_col = pl.BlockSpec((1, n, sb), lambda i, ly: (i, 0, 0))
    state_spec = pl.BlockSpec((1, sb, n, d), lambda i, ly: (ly[0], i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots // sb,),
        in_specs=[per_slot, per_slot,
                  pl.BlockSpec((n, d), lambda i, ly: (0, 0)),
                  per_col, per_col, state_spec],
        out_specs=[per_slot, state_spec],
    )
    y, state_all = pl.pallas_call(
        functools.partial(_step_kernel, slots=sb,
                          width=_channel_block(d)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, d), F32),
                   jax.ShapeDtypeStruct(state_all.shape, state_all.dtype)],
        # Operand 6 counts the scalar-prefetch layer, u, dt, a, b and c.
        input_output_aliases={6: 1},
        interpret=interpret,
        name="mamba1_step",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=step_cost(slots, n, d),
    )(jnp.asarray(layer, jnp.int32).reshape(1), u.astype(F32),
      dt.astype(F32), a_t.astype(F32), columns(b), columns(c), state_all)
    return y, state_all


def mamba1_step(state_all, layer, u, dt, a_t, b, c, *,
                use_kernel: Optional[bool] = None):
    """Advance every slot's state of layer ``layer`` (a traced int32
    scalar) by one token. ``state_all`` [L, B, N, D] float32 is the
    whole state cache; u [B, D]; dt [B, D] float32; a_t [N, D]; b, c
    [B, N]. Returns (y [B, D] float32, the updated cache). With the
    kernel the cache is updated in place and no slab of it exists;
    without, the layer's slab is sliced out, updated and put back."""
    slots, d = u.shape
    n = a_t.shape[0]
    tiles = step_applicable(slots, n, d)
    interpret = interpret_default()
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and tiles
    if use_kernel and (interpret or tiles):
        return _step_fused(state_all, layer, u, dt, a_t, b, c,
                           interpret=interpret)
    slab = jax.lax.dynamic_index_in_dim(state_all, layer, 0, keepdims=False)
    y, new = mamba1_step_reference(slab, u, dt, a_t, b, c)
    return y, jax.lax.dynamic_update_index_in_dim(state_all, new, layer, 0)
