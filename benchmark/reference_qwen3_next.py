"""The plain reference for the Qwen3-Next family (``qwen3_next``:
Qwen3-Next-80B-A3B-Instruct): its equations in float32, for the SHARE of
the model one chip holds.

Straightforward ``jax.numpy`` at matmul precision ``highest``: the delta
rule as the per-token loop (no chunking, no carried state between
pieces, no cache, no kernel), attention over every key with a mask, no
sort, no grouped multiplication, no batching. One layer and one expert
at a time; attention a block of queries at a time, so 2,000 positions
fit. It imports nothing from the program. ``tests/test_qwen3_next.py``
holds it to ``transformers``' ``qwen3_next`` modeling code on the CPU
with the weights copied across (logits), the whole model uncut.

The equations (ISSUE 38). Token ids ``t``; ``rms0(x; w) = x /
sqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred); layer ``i`` is
"linear_attention" unless ``(i + 1) % full_attention_interval == 0``:

* ``x0 = embed[t]``; ``x = x + mixer(rms0(x; w_in))``; ``x = x +
  moe(rms0(x; w_mlp))``; ``logits = rms0(x; w_final) W_head`` (untied).
* Linear mixer (Gated DeltaNet), ``Hk`` key heads of ``Dk``, ``Hv``
  value heads of ``Dv``: ``[q | k | v | z] = h W_in``, ``[b | a] = h
  W_ba``; ``q|k|v`` through a causal depthwise conv of ``K`` taps (no
  bias), then SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)`` a VALUE head; q and k L2-normalised (eps 1e-6) a head, each
  key head serving ``Hv / Hk`` consecutive value heads, ``q *= Dk^-0.5``.
  A head's state ``S [Dk, Dv]``: ``S = exp(g_t) S; u = (v_t - k_t^T S)
  beta_t; S += k_t u^T; o_t = q_t^T S``. Output ``rms(o_t) * w *
  silu(z_t)`` a head (NOT zero-centred; norm before gate), then ``W_o``.
* Full mixer: ``q, gate`` a head from two projections of ``h`` (the
  checkpoint's one ``q_proj`` twice as wide, split a head); ``k, v`` of
  ``KVH`` heads; ``rms0`` over each head's dims of q and k; rotate-half
  rope over the first ``partial_rotary_factor * D`` dims; causal softmax
  at ``D^-0.5``; ``o * sigmoid(gate)``; ``W_o``.
* MoE: ``p = softmax(h W_r)`` over ALL experts in float32, top ``k``,
  divided by their sum; SwiGLU experts; plus ``sigmoid(h w_sg) *
  SwiGLU_shared(h)``.

THE SHARE: the tree holds experts ``[first, first + count)`` of the
router's width (``config.experts_held``) and a slice of the vocabulary.
The router still scores every expert; a chosen expert that is not held
adds nothing here (it is another chip's part of the sum), and the
partial result is what goes on to the next layer. With every expert held
this is the whole layer, which the share test adds the shares up to.

Departure: ties among router scores break towards the lower expert
index (``jax.lax.top_k``).

It reads the program's parameter tree (``models/llama.py::
_init_windowed_params`` with ``models/gated_delta.py::init_mixer``):
``embed [V, E]``, ``lm_head [E, V]``, ``final_norm``, ``layers`` =
``moe_gate``/``moe_up [L, held, E, M]``, ``moe_down [L, held, M, E]``,
and ``runs``: one tree a run of equal layers, holding ``attn_norm``,
``mlp_norm [E]``, ``w_router [E, X]``, ``shared_gate``/``shared_up [E,
Ms]``, ``shared_down [Ms, E]``, ``shared_gate_w [E]`` and either
``gdn_in [E, 2 Hk Dk + 2 Hv Dv]``, ``gdn_ba [E, 2 Hv]``, ``conv_w [K, 2
Hk Dk + Hv Dv]``, ``dt_bias``/``a_log [Hv]``, ``gdn_norm [Dv]``,
``gdn_out [Hv Dv, E]`` or ``wq``/``wg [E, H, D]``, ``wk``/``wv [E, KVH,
D]``, ``q_norm``/``k_norm [D]``, ``wo [H, D, E]``. ``config`` needs
``num_layers``, ``layer_types``, ``num_heads``, ``num_kv_heads``,
``head_dim``, ``partial_rotary_factor``, ``rope_theta``, ``rms_eps``,
``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``num_experts_per_tok``, ``experts_held``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms0(x, weight, eps):
    """Zero-centred RMSNorm: scaled by ``1 + weight``."""
    x = x.astype(F32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(F32))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear_mixer(h, layer, config):
    """Gated DeltaNet on normed ``h [S, E]``: the plain per-token loop."""
    c = config
    s = h.shape[0]
    hk, hv = int(c.linear_num_key_heads), int(c.linear_num_value_heads)
    dk, dv = int(c.linear_key_head_dim), int(c.linear_value_head_dim)
    taps = int(c.linear_conv_kernel_dim)
    key_dim, value_dim = hk * dk, hv * dv
    proj = h @ layer["gdn_in"].astype(F32)
    ba = h @ layer["gdn_ba"].astype(F32)
    qkv, z = proj[:, :2 * key_dim + value_dim], proj[:, 2 * key_dim
                                                     + value_dim:]
    w = layer["conv_w"].astype(F32)                  # [K, C], tap K-1 = now
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(taps)))
    q = _l2norm(qkv[:, :key_dim].reshape(s, hk, dk))
    k = _l2norm(qkv[:, key_dim:2 * key_dim].reshape(s, hk, dk))
    q = jnp.repeat(q, hv // hk, axis=1) * dk ** -0.5
    k = jnp.repeat(k, hv // hk, axis=1)
    v = qkv[:, 2 * key_dim:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(layer["a_log"].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + layer["dt_bias"].astype(F32))

    def token(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs          # one position
        state = state * jnp.exp(g_t)[:, None, None]
        u = (v_t - jnp.sum(state * k_t[:, :, None], axis=1)) * beta_t[:, None]
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), F32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + float(c.rms_eps))
    o = o * layer["gdn_norm"].astype(F32) * jax.nn.silu(z.reshape(s, hv, dv))
    return o.reshape(s, value_dim) @ layer["gdn_out"].astype(F32)


def _rope(x, theta: float, turned: int):
    """x [S, H, D] at positions 0..S-1: the first ``turned`` dims
    rotated, pairing dim j with j + turned/2; the rest pass through."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, turned, 2, dtype=F32) / turned)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., turned:]], -1)


def _full_mixer(h, layer, config):
    c = config
    s, eps = h.shape[0], float(c.rms_eps)
    heads, kvh, d = int(c.num_heads), int(c.num_kv_heads), int(c.head_dim)
    turned = int(d * float(c.partial_rotary_factor))
    q = jnp.einsum("se,ehd->shd", h, layer["wq"].astype(F32))
    gate = jnp.einsum("se,ehd->shd", h, layer["wg"].astype(F32))
    k = jnp.einsum("se,ehd->shd", h, layer["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", h, layer["wv"].astype(F32))
    q = _rope(_rms0(q, layer["q_norm"], eps), float(c.rope_theta), turned)
    k = _rope(_rms0(k, layer["k_norm"], eps), float(c.rope_theta), turned)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    j = jnp.arange(s)
    outs = []
    for at in range(0, s, QUERY_BLOCK):          # a block of queries
        seen = j[None, :] <= j[at:at + QUERY_BLOCK, None]
        scores = jnp.einsum("qhd,khd->hqk", q[at:at + QUERY_BLOCK], k
                            ) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    o = jnp.concatenate(outs) * jax.nn.sigmoid(gate)
    return jnp.einsum("shd,hde->se", o, layer["wo"].astype(F32))


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


def _route(h, w_router, *, top_k: int):
    probs = jax.nn.softmax(h @ w_router.astype(F32), axis=-1)
    picked, chosen = jax.lax.top_k(probs, top_k)
    return picked / jnp.sum(picked, -1, keepdims=True), chosen


def _layer(x, kind: str, layer, experts, config) -> Tuple[Any, Any]:
    c = config
    eps = float(c.rms_eps)
    h = _rms0(x, layer["attn_norm"], eps)
    x = x + (_linear_mixer(h, layer, c) if kind == "linear_attention"
             else _full_mixer(h, layer, c))
    h = _rms0(x, layer["mlp_norm"], eps)
    weights, chosen = _route(h, layer["w_router"],
                             top_k=int(c.num_experts_per_tok))
    out = jax.nn.sigmoid(h @ layer["shared_gate_w"].astype(F32))[:, None] * (
        _swiglu(h, layer["shared_gate"], layer["shared_up"],
                layer["shared_down"]))
    first = c.experts_held[0] if c.experts_held else 0
    for e in range(experts["moe_gate"].shape[0]):   # the held experts
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                         axis=-1)
        out = out + weight[:, None] * _swiglu(
            h, experts["moe_gate"][e], experts["moe_up"][e],
            experts["moe_down"][e])
    return x + out, chosen


def _forward(params: Dict[str, Any], tokens, config, rows=None):
    """(logits at positions ``rows`` (all when None), router choices
    ``[L, S, k]``)."""
    c = config
    kinds = tuple(c.layer_types)
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[jnp.asarray(tokens)]
        choices, run, at = [], -1, 0
        for i, kind in enumerate(kinds):
            if i == 0 or kind != kinds[i - 1]:
                run, at = run + 1, 0        # the next run's tree, from 0
            layer = jax.tree.map(lambda a, at=at: a[at],
                                 params["runs"][run])
            experts = jax.tree.map(lambda a, li=i: a[li], params["layers"])
            x, chosen = _layer(x, kind, layer, experts, c)
            choices.append(chosen)
            at += 1
        if rows is not None:
            x = x[rows]
        x = _rms0(x, params["final_norm"], float(c.rms_eps))
        lg = x @ params["lm_head"].astype(F32)
    return lg, jnp.stack(choices)


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence of token ids ``[S]``."""
    return _forward(params, tokens, config)[0]


def router_choices(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """The experts each position routed to, ``[L, S, k]`` int32 over the
    router's whole width; compare them as SETS."""
    return _forward(params, tokens, config)[1]


def gaps_and_routes(params: Dict[str, Any], prompt, chosen, config,
                    pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: (for each
    chosen token, how far its reference logit lies under the reference
    maximum at that position, in standard deviations of that position's
    logits ``[n]``; the experts each DECODED position routed to, the
    positions ``chosen[:-1]`` were fed at, ``[n - 1, L, k]``: compare
    them as sets). The head runs on the chosen positions alone.
    ``pad_to`` pads at the end (a causal model: what follows a position
    cannot change it)."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg, choices = _forward(params, padded, config,
                           rows=slice(len(prompt) - 1, len(seq)))
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    return ((jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1),
            jnp.swapaxes(choices[:, len(prompt):len(seq)], 0, 1))
