"""The Mamba-2 token mixer of the Granite 4.0-H family, as the engine
runs it: a whole-prompt form for prefill and a one-token form for the
decode tick, over the same equations.

Read from ``transformers`` 4.57 ``modeling_granitemoehybrid.py``,
``GraniteMoeHybridMambaLayer.torch_forward`` (lines 638-842) and
``GraniteMoeHybridRMSNormGated`` (868-883), on the layer's normed input
``h [.., E]``::

    [z | xBC | dt] = h W_in                      (d_inner | conv_dim | H)
    xBC = silu(conv(xBC) + b)                    depthwise, causal, K taps
    [x | B | C] = xBC                            (d_inner | G N | G N)
    dt = softplus(dt + dt_bias)                  no clamp: the limits are (0, inf)
    h_t = exp(dt A) h_{t-1} + dt x B^T,  y = h_t C + D x       A = -exp(A_log)
    y = RMSNorm(y * silu(z)) * w                 over all d_inner, gate FIRST
    out = y W_out

A layer's parameters (one entry of ``params["runs"]``, see
``llama._init_hybrid_params``): ``ssm_in [E, 2 d_inner + 2 G N + H]``,
``conv_w [K, conv_dim]`` (tap K-1 on the current token), ``conv_b``,
``dt_bias``/``a_log``/``ssm_d [H]`` float32, ``ssm_norm [d_inner]``,
``ssm_out [d_inner, E]``.

What a request keeps between tokens (``paged_kv.StateCache``): the
recurrent state, float32, and the last ``K - 1`` inputs of the
convolution, in the model's dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm

F32 = jnp.float32

# Positions of the prefill scan that one [Q, Q] product covers: rounding
# and scratch only (48 rows x 128 heads x 128 x 128 float32 = 403 MB), not
# the result. The published ``mamba_chunk_size`` (256) is the CUDA
# kernel's parameter, not a shape of the model.
CHUNK = 128


def _in_proj(h, layer, c):
    """(z [.., d_inner], xBC [.., conv_dim], dt [.., H]) of normed h."""
    inner, conv_dim = c.mamba_dims
    with jax.named_scope("ssm/in_proj"):
        proj = jnp.einsum("bse,ef->bsf", h, layer["ssm_in"].astype(c.dtype))
    return (proj[..., :inner], proj[..., inner:inner + conv_dim],
            proj[..., inner + conv_dim:])


def _split_conv(xbc, c):
    """Convolved xBC [..., conv_dim] -> x [..., H, P], B, C [..., G, N]."""
    inner, _ = c.mamba_dims
    gn = c.mamba_n_groups * c.mamba_d_state
    lead = xbc.shape[:-1]
    return (xbc[..., :inner].reshape(*lead, c.mamba_n_heads, c.mamba_d_head),
            xbc[..., inner:inner + gn].reshape(
                *lead, c.mamba_n_groups, c.mamba_d_state),
            xbc[..., inner + gn:].reshape(
                *lead, c.mamba_n_groups, c.mamba_d_state))


def _time_step(dt, layer):
    return jax.nn.softplus(dt.astype(F32) + layer["dt_bias"].astype(F32))


def _gate_out(y, x, z, layer, c):
    """``(y + D x)``, gated by ``silu(z)``, normed over all of d_inner,
    projected out. y, x [B, S, H, P] (y float32); z [B, S, d_inner]."""
    with jax.named_scope("ssm/gate_norm"):
        y = y + layer["ssm_d"].astype(F32)[:, None] * x.astype(F32)
        y = y.reshape(*z.shape) * jax.nn.silu(z.astype(F32))
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), axis=-1, keepdims=True) + c.rms_eps)
        y = (y * layer["ssm_norm"].astype(F32)).astype(c.dtype)
    with jax.named_scope("ssm/out_proj"):
        return jnp.einsum("bsf,fe->bse", y, layer["ssm_out"].astype(c.dtype))


def mixer_prefill(h, layer, c, lengths):
    """The mixer over whole right-padded rows from an empty history:
    h [N, S, E] normed, ``lengths`` [N] the real tokens of each row.
    Returns (out [N, S, E], state [N, H, N_s / f, f P] float32 in the
    cache's packed layout, conv tail [N, K - 1, conv_dim]): the state
    and the tail as they stand after each row's LAST REAL token.
    Positions past it get a zero time step, which leaves the state as it
    is; their outputs are never read."""
    z, xbc_in, dt = _in_proj(h, layer, c)
    k = c.mamba_d_conv
    with jax.named_scope("ssm/conv"):
        xbc = jax.nn.silu(ssm.causal_conv(
            xbc_in, layer["conv_w"], layer["conv_b"])).astype(c.dtype)
        tail = ssm.conv_tail(xbc_in, lengths, k)
    x, b, cc = _split_conv(xbc, c)
    real = jnp.arange(h.shape[1])[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], _time_step(dt, layer), 0.0)
    with jax.named_scope("ssm/scan"):
        y, state = ssm.ssm_chunked_scan(
            x, dt, -jnp.exp(layer["a_log"].astype(F32)), b, cc,
            chunk=CHUNK, dtype=c.dtype)
    return _gate_out(y, x, z, layer, c), ssm.pack_state(state), tail


def mixer_step(h, layer, c, state_all, conv_all, index, use_kernel=None):
    """The mixer on ONE token a slot, advancing every slot's state:
    h [B, 1, E] normed; ``state_all`` [L_ssm, B, H, N_s / f, f P] float32
    and ``conv_all`` [L_ssm, B, K - 1, conv_dim], the whole state cache,
    read and written at layer ``index`` (a traced int32 scalar). Returns
    (out [B, 1, E], state_all, conv_all)."""
    z, xbc_in, dt = _in_proj(h, layer, c)
    with jax.named_scope("ssm/conv"):
        tail = jax.lax.dynamic_index_in_dim(conv_all, index, 0,
                                            keepdims=False)
        xbc, tail = ssm.conv_step(tail, xbc_in[:, 0], layer["conv_w"],
                                  layer["conv_b"])
        xbc = jax.nn.silu(xbc).astype(c.dtype)
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, tail, index, 0)
    x, b, cc = _split_conv(xbc, c)
    with jax.named_scope("ssm/step"):
        y, state_all = ssm.ssm_step(
            state_all, index, x, _time_step(dt[:, 0], layer),
            -jnp.exp(layer["a_log"].astype(F32)), b, cc,
            use_kernel=use_kernel)
    out = _gate_out(y[:, None], x[:, None], z, layer, c)
    return out, state_all, conv_all
