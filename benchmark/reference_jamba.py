"""The plain reference for the Jamba family at ``num_experts`` 1
(``model_type`` "jamba": AI21-Jamba2-3B), its equations in float32
(ISSUE 55, Tentpole 2).

Straightforward ``jax.numpy`` at matmul precision ``highest``: no cache,
no kernel, no batching, no chunks; one whole sequence, attention under a
causal mask, the selective scan as the per-token loop it is. It imports
nothing from the program.

The equations (``transformers`` 4.57 ``modeling_jamba.py``:
``JambaMambaDecoderLayer``, ``JambaAttentionDecoderLayer``,
``JambaMambaMixer.slow_forward``, ``JambaAttention``, ``JambaMLP``).
Token ids ``t``; ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``. ``x =
embed[t]``. For layer ``i = 0 .. L - 1``:

* ``x = x + Mixer_i(N(x; w_in))``, the mixer attention where
  ``layer_types[i]`` says so (the published ``i % attn_layer_period ==
  attn_layer_offset``), Mamba-1 elsewhere;
* ``x = x + Wd (silu(Wg b) * Wu b)``, ``b = N(x; w_ff)``: a dense SwiGLU
  on EVERY layer (``layers_num_experts`` all 1).
* ``logits = N(x; w_final) embed^T`` (tied).

Attention: ``q, k, v = a Wq, a Wk, a Wv`` (no bias), NO position
encoding, ``softmax(q k^T / sqrt(d), causal) v``, every query head on
the K/V head of its group, then ``Wo``.

Mamba-1, on normed ``a [S, E]``, ``Di`` channels, ``N`` states, rank
``R``, ``K`` taps::

    [u | z] = a W_in
    u = silu(conv_K(u) + b_conv)            causal, depthwise, over u alone
    [r | B | C] = u W_x ; r, B, C = N(r; w_dt), N(B; w_b), N(C; w_c)
    dt = softplus(r W_dt + b_dt) ;  A = -exp(A_log)
    s_t[d, n] = exp(dt_t[d] A[d, n]) s_{t-1}[d, n] + dt_t[d] B_t[n] u_t[d]
    y_t[d] = sum_n s_t[d, n] C_t[n] + D[d] u_t[d]
    out = (y * silu(z)) W_out

Departure from ``modeling_jamba.py``, the only one: ``slow_forward``
rounds the state ``s`` to the model's dtype before the product with C;
here, as in the program, it stays float32 (in float32 they are the same
function, which is what ``tests/test_jamba.py`` holds this file to).

It reads the program's parameter tree (``models/llama.py::
_init_jamba_params``): ``embed [V, E]``, ``final_norm [E]`` and
``runs``, one tree a run of equal layers, stacked ``[n, ...]``:
``attn_norm`` (w_in), ``mlp_norm`` (w_ff) ``[E]``, ``w_gate``/``w_up
[E, M]``, ``w_down [M, E]``, and either ``wq [E, H, D]``, ``wk``/``wv
[E, KVH, D]``, ``wo [H, D, E]`` or the mixer's ``m1_in [E, 2 Di]``,
``conv_w [K, Di]`` (tap K-1 on the current token), ``conv_b [Di]``,
``m1_x [Di, R + 2 N]``, ``dt_norm [R]``, ``b_norm``/``c_norm [N]``,
``m1_dt [R, Di]``, ``dt_bias``/``m1_d [Di]``, ``a_log [N, Di]`` (the
checkpoint's ``A_log`` transposed), ``m1_out [Di, E]``. ``config``
needs ``layer_types``, ``num_heads``, ``num_kv_heads``,
``mamba_dt_rank``, ``mamba_d_state``, ``rms_eps``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Queries scored at a time: [H, block, S] float32 scores.
QUERY_BLOCK = 512


def _norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def attention(a, layer, heads: int, kv_heads: int):
    """Causal attention without positions on normed ``a [S, E]``:
    ``[S, E]`` after ``Wo``."""
    s = a.shape[0]
    q = jnp.einsum("se,ehd->shd", a, layer["wq"].astype(F32))
    k = jnp.einsum("se,ehd->shd", a, layer["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", a, layer["wv"].astype(F32))
    d = q.shape[-1]
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    at = jnp.arange(s)
    outs = []
    for first in range(0, s, QUERY_BLOCK):       # a block of queries
        rows = at[first:first + QUERY_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", q[first:first + QUERY_BLOCK],
                            k) / d ** 0.5
        seen = at[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.einsum("shd,hde->se", jnp.concatenate(outs),
                      layer["wo"].astype(F32))


def mamba(a, layer, rank: int, states: int, eps: float):
    """The Mamba-1 mixer on normed ``a [S, E]``: (``[S, E]`` after
    ``W_out``, the state after the last position ``[Di, N]``)."""
    proj = a @ layer["m1_in"].astype(F32)
    inner = proj.shape[-1] // 2
    u, z = proj[:, :inner], proj[:, inner:]
    w = layer["conv_w"].astype(F32)                 # [K, Di]
    taps = w.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    conv = layer["conv_b"].astype(F32) + sum(
        padded[j:j + u.shape[0]] * w[j] for j in range(taps))
    u = jax.nn.silu(conv)
    rbc = u @ layer["m1_x"].astype(F32)
    r = _norm(rbc[:, :rank], layer["dt_norm"], eps)
    b = _norm(rbc[:, rank:rank + states], layer["b_norm"], eps)
    c = _norm(rbc[:, rank + states:], layer["c_norm"], eps)
    dt = jax.nn.softplus(r @ layer["m1_dt"].astype(F32)
                         + layer["dt_bias"].astype(F32))    # [S, Di]
    a_neg = -jnp.exp(layer["a_log"].astype(F32)).T          # [Di, N]

    def token(s, inputs):                           # s [Di, N]
        dt_t, u_t, b_t, c_t = inputs
        s = (jnp.exp(dt_t[:, None] * a_neg) * s
             + (dt_t * u_t)[:, None] * b_t[None, :])
        return s, s @ c_t

    state, y = jax.lax.scan(token, jnp.zeros((inner, states), F32),
                            (dt, u, b, c))
    y = y + layer["m1_d"].astype(F32) * u
    return (y * jax.nn.silu(z)) @ layer["m1_out"].astype(F32), state


@functools.partial(jax.jit, static_argnames=("kind", "heads", "kv_heads",
                                             "rank", "states", "eps"))
def layer_application(x, run, at, kind: str, heads: int, kv_heads: int,
                      rank: int, states: int, eps: float):
    """Layer ``at`` of the stacked ``run`` on ``x [S, E]``: the mixer and
    the SwiGLU. Returns (x, the mixer's final state or None)."""
    layer = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, at, 0, keepdims=False), run)
    with jax.default_matmul_precision("highest"):
        a = _norm(x, layer["attn_norm"], eps)
        if kind == "attention":
            mixed, state = attention(a, layer, heads, kv_heads), None
        else:
            mixed, state = mamba(a, layer, rank, states, eps)
        x = x + mixed
        b = _norm(x, layer["mlp_norm"], eps)
        return x + (jax.nn.silu(b @ layer["w_gate"].astype(F32))
                    * (b @ layer["w_up"].astype(F32))
                    ) @ layer["w_down"].astype(F32), state


@functools.partial(jax.jit, static_argnames=("eps", "rows"))
def _head(x, final_norm, embed, eps: float,
          rows: Optional[Tuple[int, int]]):
    with jax.default_matmul_precision("highest"):
        h = _norm(x, final_norm, eps)
        at = h if rows is None else h[rows[0]:rows[0] + rows[1]]
        return at @ embed.astype(F32).T


def _runs(config):
    """(kind, how many) for each run of equal layers, in order."""
    out = []
    for kind in config.layer_types:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return out


def forward(params: Dict[str, Any], tokens, config,
            rows: Optional[Tuple[int, int]] = None):
    """One sequence ``tokens [S]``: (float32 logits ``[S or rows, V]``,
    the Mamba layers' states after the last position, a list of
    ``[Di, N]``)."""
    c = config
    x = params["embed"].astype(F32)[jnp.asarray(tokens)]
    states = []
    for (kind, count), run in zip(_runs(c), params["runs"]):
        for at in range(count):
            x, state = layer_application(
                x, run, at, kind, int(c.num_heads), int(c.num_kv_heads),
                int(c.mamba_dt_rank), int(c.mamba_d_state), float(c.rms_eps))
            if state is not None:
                states.append(state)
    return _head(x, params["final_norm"], params["embed"], float(c.rms_eps),
                 rows), states


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence."""
    return forward(params, tokens, config)[0]


def gaps(params: Dict[str, Any], prompt, chosen, config, pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: for each chosen
    token, how far its reference logit lies under the reference maximum
    at that position, in standard deviations of that position's logits,
    ``[n]``. ``pad_to`` pads at the end (what follows a position cannot
    change it), so requests of one length share compiled programs."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg, _ = forward(params, jnp.asarray(padded, jnp.int32), config,
                    rows=(len(prompt) - 1, len(chosen)))
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1)[:, 0]
    return (jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1)
