"""The plain reference for the Ouro family (``model_type`` "ouro": Ouro
2.6B, a looped language model), its equations in float32 (ISSUE 53,
Tentpole 2).

Straightforward ``jax.numpy`` at matmul precision ``highest``: no cache,
no kernel, no batching, one whole sequence under a causal mask, and the
``T x L`` layer applications written as two plain loops. It imports
nothing from the program.

The equations. Token ids ``t``; ``N(x; w) = x / sqrt(mean(x^2) + eps) *
w`` (a plain RMSNorm: the weight, not ``1 + w``). ``x = embed[t]``. For
step ``s = 0 .. T - 1`` (``T = total_ut_steps``), for layer ``l = 0 .. L
- 1``, THE SAME weights in every step:

* ``a = N(x; w1_l)``; ``q, k, v = a Wq, a Wk, a Wv`` (no bias), heads of
  ``d`` dims; ``q, k`` rotated (rotate-half over all ``d`` dims, theta
  ``rope_theta``, no scaling) at the token's own position, the same in
  every step; ``o = softmax(q k^T / sqrt(d), causal) v`` over the keys
  THIS step made in this layer: a step never sees another step's keys;
  ``x = x + N(o Wo; w2_l)``;
* ``b = N(x; w3_l)``; ``x = x + N((silu(b Wg) * b Wu) Wd; w4_l)``.
  Four norms a layer: before each sublayer and on its OUTPUT before the
  sum (the checkpoint's ``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``).
* After layer ``L - 1`` of EVERY step: ``h_s = N(x; w_final)``, and ``x =
  h_s`` is the next step's input: the final norm stands between the
  steps, not only before the head. ``lambda_s = sigmoid(h_s . w_e +
  b_e)``, one scalar a token a step (``early_exit_gate``); ``logits_s =
  h_s W_head`` (untied).
* ``p_s = lambda_s prod_{j<s} (1 - lambda_j)`` for ``s < T - 1``, ``p_{T-1}``
  the remainder; inference leaves at the first ``s`` whose running sum
  reaches ``early_exit_threshold`` (:func:`exit_step`). At the published
  threshold 1 that is the last step: ``logits = logits_{T-1}``.

Departures from the published description: none that this file knows
of, but every line above that is not a key of ``config.json`` (the place
of the four norms, the final norm between steps, a step's keys being
its own, the gate's form) is written from the paper (arXiv:2510.25741)
and the checkpoint's modeling file AS RECALLED, without network access:
the configuration file's ``assumed`` lists them key by key. The paper
also describes sharing ONE step's K/V among the steps at decode; that is
another output and not this reference.

It reads the program's parameter tree (``models/llama.py::
_init_looped_params``): ``embed [V, E]``, ``lm_head [E, V]``,
``final_norm [E]``, ``exit_gate_w [E]``, ``exit_gate_b []`` and
``layers``, stacked ``[L, ...]``: ``attn_norm`` (w1), ``post_attn_norm``
(w2), ``mlp_norm`` (w3), ``post_mlp_norm`` (w4) ``[E]``, ``wq [E, H,
D]``, ``wk``/``wv [E, KVH, D]``, ``wo [H, D, E]``, ``w_gate``/``w_up [E,
M]``, ``w_down [M, E]``. ``config`` needs ``num_layers``, ``loop_steps``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``rope_theta``,
``rms_eps``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# Queries scored at a time: [H, block, S] float32 scores.
QUERY_BLOCK = 512


def _norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def _rope(x, theta: float):
    """x [S, H, D] at positions 0..S-1, rotate-half: dim j pairs with
    j + D / 2."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(a, layer, heads: int, kv_heads: int, theta: float):
    """Causal attention on normed ``a [S, E]``: ``[S, E]`` after ``Wo``."""
    s = a.shape[0]
    q = jnp.einsum("se,ehd->shd", a, layer["wq"].astype(F32))
    k = jnp.einsum("se,ehd->shd", a, layer["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", a, layer["wv"].astype(F32))
    d = q.shape[-1]
    q, k = _rope(q, theta), _rope(k, theta)
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


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps"))
def layer_application(x, layers, at, heads: int, kv_heads: int, theta: float,
                      eps: float):
    """Layer ``at`` of the stacked ``layers`` on ``x [S, E]``: the two
    sublayers, four norms."""
    layer = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, at, 0, keepdims=False), layers)
    with jax.default_matmul_precision("highest"):
        o = attention(_norm(x, layer["attn_norm"], eps), layer, heads,
                      kv_heads, theta)
        x = x + _norm(o, layer["post_attn_norm"], eps)
        b = _norm(x, layer["mlp_norm"], eps)
        down = (jax.nn.silu(b @ layer["w_gate"].astype(F32))
                * (b @ layer["w_up"].astype(F32))
                ) @ layer["w_down"].astype(F32)
        return x + _norm(down, layer["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "rows"))
def _step_end(x, final_norm, gate_w, gate_b, head, eps: float,
              rows: Optional[Tuple[int, int]]):
    """After a step's last layer: ``h``, the gate of every position and
    the logits of positions ``rows`` = (first, how many) (all if None)."""
    with jax.default_matmul_precision("highest"):
        h = _norm(x, final_norm, eps)
        gate = jax.nn.sigmoid(h @ gate_w.astype(F32) + gate_b.astype(F32))
        at = h if rows is None else h[rows[0]:rows[0] + rows[1]]
        return h, gate, at @ head.astype(F32)


def forward(params: Dict[str, Any], tokens, config,
            rows: Optional[Tuple[int, int]] = None):
    """One sequence ``tokens [S]`` through the ``T`` steps: (logits ``[T,
    S or rows, V]``, gates ``[T, S]``), float32, a step an entry."""
    c = config
    x = params["embed"].astype(F32)[jnp.asarray(tokens)]
    layers = params["layers"]
    logits, gates = [], []
    for _step in range(int(c.loop_steps)):
        for at in range(int(c.num_layers)):
            x = layer_application(
                x, layers, at, int(c.num_heads), int(c.num_kv_heads),
                float(c.rope_theta), float(c.rms_eps))
        x, gate, lg = _step_end(
            x, params["final_norm"], params["exit_gate_w"],
            params["exit_gate_b"], params["lm_head"], float(c.rms_eps), rows)
        logits.append(lg)
        gates.append(gate)
    return jnp.stack(logits), jnp.stack(gates)


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of the LAST step: what inference at the
    published ``early_exit_threshold`` of 1 samples from."""
    return forward(params, tokens, config)[0][-1]


def exit_step(gates, threshold: float) -> np.ndarray:
    """The step at which each position leaves the loop: the first whose
    running sum of ``p`` reaches ``threshold``; ``gates [T, S]`` -> ``[S]``.
    At 1 (published) that is ``T - 1`` everywhere."""
    gates = np.asarray(gates, np.float64)
    reached = 1.0 - np.cumprod(1.0 - gates, axis=0)
    reached[-1] = 1.0
    return np.argmax(reached >= threshold, axis=0)


def gaps(params: Dict[str, Any], prompt, chosen, config, pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: for each chosen
    token, how far its reference logit lies under the reference maximum
    at that position, in standard deviations of that position's logits,
    ``[n]``; and the reference's gates at those positions ``[T, n]``.
    ``pad_to`` pads at the end (what follows a position cannot change
    it), so requests of one length of answer share compiled programs."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    first, count = len(prompt) - 1, len(chosen)
    lg, gates = forward(params, jnp.asarray(padded, jnp.int32), config,
                        rows=(first, count))
    lg = lg[-1]
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen, jnp.int32)[:, None], axis=-1)[:, 0]
    return ((jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1),
            gates[:, first:first + count])
