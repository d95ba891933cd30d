"""The Mamba-1 token mixer of the Jamba family (``layer_types``
"mamba1"; ``transformers`` calls the layer "mamba", which in this
program means Mamba-2), as the engine runs it: a form for whole rows or
for a CHUNK that goes on from a carried state and conv tail, and a
one-token form for the decode tick; the engine's two forwards dispatch
to them where they meet the kind (``continuous_batching._forward_paged``,
``_prefill_forward_paged``).

Read from ``transformers`` 4.57 ``modeling_jamba.py``,
``JambaMambaMixer.slow_forward`` (lines 725-808), on the layer's normed
input ``h [.., E]``, with ``Di`` inner channels, ``N`` states a channel,
a time step of rank ``R`` and ``K`` conv taps::

    [u | z] = h W_in                             (Di | Di), no bias
    u = silu(conv(u) + b_conv)                   depthwise, causal, over u ALONE
    [r | B | C] = u W_x                          (R | N | N), no bias
    r, B, C = rmsnorm(r) w_dt, rmsnorm(B) w_b, rmsnorm(C) w_c
    dt = softplus(r W_dt + b_dt)                 [Di], float32
    s_t = exp(dt A) s_{t-1} + dt u B^T,  y = s_t C + D u       A = -exp(A_log)
    out = (y * silu(z)) W_out                    no norm after the gate

A layer's parameters (one entry of ``params["runs"]``,
:func:`init_mixer`): ``m1_in [E, 2 Di]``, ``conv_w [K, Di]`` (tap K-1 on
the current token), ``conv_b [Di]``, ``m1_x [Di, R + 2 N]``,
``dt_norm [R]``, ``b_norm``/``c_norm [N]``, ``m1_dt [R, Di]``,
``dt_bias``/``m1_d [Di]`` float32, ``a_log [N, Di]`` float32 (the
checkpoint's ``A_log`` TRANSPOSED, channels on lanes: a loader's
transpose), ``m1_out [Di, E]``. ``LlamaConfig`` holds ``Di`` as ONE
head (``mamba_n_heads`` 1 x ``mamba_d_head``): Mamba-1 has none.

What a request keeps between tokens (``paged_kv.StateCache``): the
state ``[N, Di]``, float32 whatever the model's dtype (``slow_forward``
rounds it to the model's dtype before the product with C; this does not),
and the convolution's last ``K - 1`` inputs, in the model's dtype. A
prefill CHUNK that is not a prompt's first reads both from the slot's
row, where the chunk before it left them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.gated_delta import _next_tail
from ray_tpu.ops import selective_scan, ssm

F32 = jnp.float32


def state_shapes(c):
    """A slot's (recurrent state, conv tail) shapes a layer. The tail's
    ``K - 1`` rows of ``Di`` lie side by side in ONE row of ``(K - 1)
    Di`` lanes: as ``[slots, K - 1, Di]`` the compiler re-lays the whole
    ``[L, slots, 3, 5120]`` cache to put slots on sublanes and back, two
    200 MB copies a tick (PR 55's first trace)."""
    inner = c.mamba_dims[0]
    return (c.mamba_d_state, inner), ((c.mamba_d_conv - 1) * inner,)


def init_mixer(c, key, n: int):
    """A run of ``n`` layers' mixer weights, stacked. Seeded so that
    dropping a term shows: ``a_log`` gives ``-exp(a_log)`` uniform in
    -1..-16 and ``dt_bias`` a softplus log-uniform in 0.001..0.1 (the
    ranges of ``mamba_ssm``'s own initialiser); ``m1_d``, the three
    inner norms' weights are uniform in 0.5..1.5, not ones, and the conv
    bias in -0.5..0.5, not zero."""
    E, inner = c.hidden_size, c.mamba_dims[0]
    N, R, K = c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    k = jax.random.split(key, 12)

    def dense(key, fan_in, *shape):
        out = jax.random.normal(key, shape, F32) * fan_in ** -0.5
        return out.astype(c.dtype)

    def uniform(key, lo, hi, *shape):
        return jax.random.uniform(key, shape, F32, lo, hi)

    dt = jnp.exp(uniform(k[6], jnp.log(1e-3), jnp.log(1e-1), n, inner))
    return {
        "m1_in": dense(k[0], E, n, E, 2 * inner),
        "conv_w": dense(k[1], K, n, K, inner),
        "conv_b": uniform(k[2], -0.5, 0.5, n, inner).astype(c.dtype),
        "m1_x": dense(k[3], inner, n, inner, R + 2 * N),
        "dt_norm": uniform(k[4], 0.5, 1.5, n, R).astype(c.dtype),
        "b_norm": uniform(k[8], 0.5, 1.5, n, N).astype(c.dtype),
        "c_norm": uniform(k[9], 0.5, 1.5, n, N).astype(c.dtype),
        "m1_dt": dense(k[5], R, n, R, inner),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus(dt_bias) = dt
        "a_log": jnp.log(uniform(k[7], 1.0, 16.0, n, N, inner)),
        "m1_d": uniform(k[10], 0.5, 1.5, n, inner),
        "m1_out": dense(k[11], inner, n, inner, E),
    }


def _in_proj(h, layer, c):
    """(u [.., Di] before the convolution, z [.., Di]) of normed h."""
    inner = c.mamba_dims[0]
    with jax.named_scope("ssm1/in_proj"):
        proj = jnp.einsum("bse,ef->bsf", h, layer["m1_in"].astype(c.dtype))
    return proj[..., :inner], proj[..., inner:]


def _rms(x, weight, c):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + c.rms_eps)
    return x * weight.astype(F32)


def _select(u, layer, c):
    """What the convolved ``u [.., Di]`` selects for its own position:
    (dt [.., Di] float32, B, C [.., N] float32)."""
    N, R = c.mamba_d_state, c.mamba_dt_rank
    with jax.named_scope("ssm1/select"):
        rbc = jnp.einsum("bsf,fr->bsr", u, layer["m1_x"].astype(c.dtype))
        r = _rms(rbc[..., :R], layer["dt_norm"], c).astype(c.dtype)
        dt = jnp.einsum("bsr,rf->bsf", r, layer["m1_dt"].astype(c.dtype),
                        preferred_element_type=F32)
        return (jax.nn.softplus(dt + layer["dt_bias"].astype(F32)),
                _rms(rbc[..., R:R + N], layer["b_norm"], c),
                _rms(rbc[..., R + N:], layer["c_norm"], c))


def _gate_out(y, u, z, layer, c):
    """``(y + D u) silu(z)`` projected out. y [B, S, Di] float32."""
    with jax.named_scope("ssm1/gate"):
        y = y + layer["m1_d"].astype(F32) * u.astype(F32)
        y = (y * jax.nn.silu(z.astype(F32))).astype(c.dtype)
    with jax.named_scope("ssm1/out_proj"):
        return jnp.einsum("bsf,fe->bse", y, layer["m1_out"].astype(c.dtype))


def _a_t(layer):
    return -jnp.exp(layer["a_log"].astype(F32))


def _conv_step(tail, new, w, bias):
    """``ssm.conv_step`` on a tail kept as ONE row a slot (oldest input
    first, :func:`state_shapes`): tail [B, (K - 1) Di], new [B, Di].
    Returns (out [B, Di] float32, the next tail). Every slice is whole
    lane tiles."""
    d = new.shape[-1]
    taps = [tail[:, j * d:(j + 1) * d] for j in range(w.shape[0] - 1)]
    taps.append(new.astype(tail.dtype))
    out = bias.astype(F32) + sum(
        tap.astype(F32) * w[j].astype(F32) for j, tap in enumerate(taps))
    return out, jnp.concatenate(taps[1:], axis=-1)


def mixer_prefill(h, layer, c, lengths, state=None, tail=None,
                  use_kernel=None):
    """The mixer over right-padded rows: h [N, S, E] normed, ``lengths``
    [N] the real tokens of each row. ``state`` [N, N_s, Di] float32 and
    ``tail`` [N, (K - 1) Di] as the rows' EARLIER chunk left them; None =
    an empty history. Returns (out [N, S, E], state, conv tail): both as
    they stand after each row's LAST REAL token. Positions past it get a
    zero time step, which leaves the state as it is, and stay out of the
    tail; their outputs are never read."""
    u_in, z = _in_proj(h, layer, c)
    k = c.mamba_d_conv
    rows, inner = h.shape[0], u_in.shape[-1]
    if tail is None:
        tail = jnp.zeros((rows, (k - 1) * inner), u_in.dtype)
    with jax.named_scope("ssm1/conv"):
        tail = tail.astype(u_in.dtype).reshape(rows, k - 1, inner)
        history = jnp.concatenate([tail, u_in], axis=1)
        u = jax.nn.silu(ssm.causal_conv(
            history, layer["conv_w"], layer["conv_b"])[:, k - 1:]
        ).astype(c.dtype)
        tail = _next_tail(tail, u_in, lengths, k).reshape(rows, -1)
    dt, b, cc = _select(u, layer, c)
    real = jnp.arange(h.shape[1])[None, :] < lengths[:, None]
    with jax.named_scope("ssm1/scan"):
        y, state = selective_scan.mamba1_scan(
            u, jnp.where(real[..., None], dt, 0.0), _a_t(layer), b, cc,
            state, use_kernel=use_kernel)
    return _gate_out(y, u, z, layer, c), state, tail


def mixer_step(h, layer, c, state_all, conv_all, index, use_kernel=None):
    """The mixer on ONE token a slot, advancing every slot's state:
    h [B, 1, E] normed; ``state_all`` [L_ssm, B, N_s, Di] float32 and
    ``conv_all`` [L_ssm, B, (K - 1) Di], the whole state cache, read and
    written at layer ``index`` (a traced int32 scalar). Returns
    (out [B, 1, E], state_all, conv_all)."""
    u_in, z = _in_proj(h, layer, c)
    with jax.named_scope("ssm1/conv"):
        tail = jax.lax.dynamic_index_in_dim(conv_all, index, 0,
                                            keepdims=False)
        u, tail = _conv_step(tail, u_in[:, 0], layer["conv_w"],
                             layer["conv_b"])
        u = jax.nn.silu(u).astype(c.dtype)[:, None]
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, tail, index, 0)
    dt, b, cc = _select(u, layer, c)
    with jax.named_scope("ssm1/step"):
        y, state_all = selective_scan.mamba1_step(
            state_all, index, u[:, 0], dt[:, 0], _a_t(layer), b[:, 0],
            cc[:, 0], use_kernel=use_kernel)
    return _gate_out(y[:, None], u, z, layer, c), state_all, conv_all
