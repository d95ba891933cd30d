"""The plain reference for the Granite 4.0-H family: its equations in
float32.

Straightforward ``jax.numpy`` at matmul precision ``highest``: no
chunked scan, no state cache, no kernel, no sort, no grouped
multiplication, no batching. One layer at a time, so only one layer's
float32 weights exist at once. It imports nothing from the program.
The equations, as the published modeling code has them (``transformers``
4.57 ``models/granitemoehybrid/modeling_granitemoehybrid.py``; class
and line with each):

* ``GraniteMoeHybridModel.forward``: ``x = embed[tokens] *
  embedding_multiplier``; after the layers a final RMSNorm; the head is
  the embedding (tied) and ``GraniteMoeHybridForCausalLM.forward``
  divides the logits by ``logits_scaling``.
* ``GraniteMoeHybridDecoderLayer.forward`` (1135-1221): ``x +=
  residual_multiplier * mixer(RMSNorm(x))``, then ``h = RMSNorm(x)``,
  ``x += residual_multiplier * (moe(h) + shared_mlp(h))``.
* ``GraniteMoeHybridAttention`` (140-224): no bias, NO rotation
  (``position_embedding_type`` "nope": ``position_embeddings`` is None),
  causal softmax with scale ``attention_multiplier``.
* ``GraniteMoeHybridMambaLayer.torch_forward`` (638-842): ``[z | xBC |
  dt] = h W_in``; ``xBC = silu(conv(xBC) + b)``, a depthwise causal
  convolution of ``mamba_d_conv`` taps; ``[x | B | C] = xBC``; ``dt =
  softplus(dt + dt_bias)`` (its clamp is to ``(0, inf)``); ``A =
  -exp(A_log)``; the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
  B_t^T``, ``y_t = h_t C_t + D x_t`` — HERE a plain ``lax.scan`` over
  positions, where the published code has the chunked form;
  ``GraniteMoeHybridRMSNormGated`` (868-883): ``y = RMSNorm(y *
  silu(z))`` over all ``d_inner`` channels, the gate applied BEFORE the
  norm; ``out = y W_out``.
* ``GraniteMoeHybridTopKGating`` (1002-1047) and ``...MoE`` (1050-1107):
  router logits in float32, the top ``k``, a softmax over THOSE ``k``
  logits; every routed position is computed (no capacity); an expert is
  ``down(silu(gate h) * up h)``. ``GraniteMoeHybridMLP`` (886-909): the
  shared SwiGLU, added unweighted.

Departures, each listed in the configuration file under ``assumed``:
weights come from the seed; ties among router logits break towards the
lower expert index (``jax.lax.top_k``), which ``torch.topk`` does not
promise (none occurs in float32 on seeded weights).

It reads the program's parameter tree (``models/llama.py::
_init_hybrid_params``): ``embed [V, E]``, ``final_norm``, ``layers`` =
the experts ``moe_gate``/``moe_up [L, X, E, M]``, ``moe_down [L, X, M,
E]`` at the GLOBAL layer index, and ``runs``, one tree a run of equal
layers stacked over the run: ``attn_norm``, ``mlp_norm``, ``w_router
[E, X]``, ``shared_gate``/``shared_up [E, Ms]``, ``shared_down [Ms,
E]``, and either ``wq [E, H, D]``, ``wk``/``wv [E, KVH, D]``, ``wo [H,
D, E]`` or ``ssm_in [E, 2 d_inner + 2 G N + H]``, ``conv_w [K,
conv_dim]`` (tap K-1 on the current token), ``conv_b``, ``dt_bias``,
``a_log``, ``ssm_d [H]``, ``ssm_norm [d_inner]``, ``ssm_out [d_inner,
E]``. ``config`` needs ``layer_types``, ``rms_eps``,
``num_experts_per_tok``, ``attention_multiplier``,
``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32

# What a layer holds for its MLP half; the rest is its mixer's.
_MLP_KEYS = ("mlp_norm", "w_router", "shared_gate", "shared_up",
             "shared_down")


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


@functools.partial(jax.jit, static_argnames=("scale", "eps"))
def _attention(x, layer, *, scale: float, eps: float):
    """The attention mixer on one sequence ``x [S, E]``: its output,
    before the residual."""
    w = jax.tree.map(lambda a: a.astype(F32), layer)
    s = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("se,ehd->shd", h, w["wq"])
    k = jnp.einsum("se,ehd->shd", h, w["wk"])
    v = jnp.einsum("se,ehd->shd", h, w["wv"])
    heads, kv_heads, d = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(s, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("qhgd,khd->hgqk", qg, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(s, heads, d)
    return jnp.einsum("shd,hde->se", o, w["wo"])


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "state", "groups", "eps"))
def _mamba(x, layer, *, heads: int, head_dim: int, state: int, groups: int,
           eps: float):
    """The Mamba-2 mixer on one sequence ``x [S, E]``: its output,
    before the residual."""
    w = jax.tree.map(lambda a: a.astype(F32), layer)
    s = x.shape[0]
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    proj = _rms_norm(x, w["attn_norm"], eps) @ w["ssm_in"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + conv_dim],
                  proj[:, inner + conv_dim:])
    taps = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim), F32), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(
        padded[j:j + s] * w["conv_w"][j] for j in range(taps)))
    xs = xbc[:, :inner].reshape(s, heads, head_dim)
    per_group = heads // groups
    b = jnp.repeat(xbc[:, inner:inner + groups * state].reshape(
        s, groups, state), per_group, axis=1)              # [S, H, N]
    c = jnp.repeat(xbc[:, inner + groups * state:].reshape(
        s, groups, state), per_group, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                 # [S, H]
    a = -jnp.exp(w["a_log"])                                # [H]

    def step(hidden, inputs):                               # [H, P, N]
        x_t, b_t, c_t, dt_t = inputs
        hidden = (jnp.exp(dt_t * a)[:, None, None] * hidden
                  + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return hidden, jnp.sum(hidden * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state), F32),
                        (xs, b, c, dt))
    y = (y + w["ssm_d"][:, None] * xs).reshape(s, inner)
    y = _rms_norm(y * jax.nn.silu(z), w["ssm_norm"], eps)
    return y @ w["ssm_out"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _route(x, mlp_norm, w_router, *, eps: float, top_k: int):
    """(h [S, E], weights [S, k], experts [S, k])."""
    h = _rms_norm(x, mlp_norm, eps)
    top, chosen = jax.lax.top_k(h @ w_router.astype(F32), top_k)
    return h, jax.nn.softmax(top, axis=-1), chosen


@jax.jit
def _expert(h, gate, up, down, weight):
    """One SwiGLU on every position, scaled by ``weight [S]`` (0 where
    the position did not route to it; ones for the shared expert)."""
    y = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
         ) @ down.astype(F32)
    return weight[:, None] * y


@jax.jit
def _routed_expert(h, experts, e, weights, chosen):
    """Expert ``e`` of one layer's ``[X, ...]`` weights on every
    position, kept where the position routed to it."""
    weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
    return _expert(h, experts["moe_gate"][e], experts["moe_up"][e],
                   experts["moe_down"][e], weight)


def _layer(x, kind: str, layer, experts, config) -> Tuple[Any, Any]:
    """One decoder layer; returns (x, the chosen experts [S, k])."""
    c = config
    eps, mult = float(c.rms_eps), float(c.residual_multiplier)
    mixer = {k: v for k, v in layer.items() if k not in _MLP_KEYS}
    if kind == "mamba":
        mixed = _mamba(x, mixer, heads=int(c.mamba_n_heads),
                       head_dim=int(c.mamba_d_head),
                       state=int(c.mamba_d_state),
                       groups=int(c.mamba_n_groups), eps=eps)
    else:
        mixed = _attention(x, mixer, scale=float(c.attention_multiplier),
                           eps=eps)
    x = x + mult * mixed
    h, weights, chosen = _route(x, layer["mlp_norm"], layer["w_router"],
                                eps=eps, top_k=int(c.num_experts_per_tok))
    out = _expert(h, layer["shared_gate"], layer["shared_up"],
                  layer["shared_down"], jnp.ones(h.shape[0], F32))
    for e in range(experts["moe_gate"].shape[0]):
        out = out + _routed_expert(h, experts, e, weights, chosen)
    return x + mult * out, chosen


def _forward(params: Dict[str, Any], tokens, config):
    c = config
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        x = embed[jnp.asarray(tokens)] * float(c.embedding_multiplier)
        choices, li, run, at = [], 0, -1, 0
        for i, kind in enumerate(c.layer_types):
            if i == 0 or kind != c.layer_types[i - 1]:
                run, at = run + 1, 0        # the next run's tree, from 0
            layer = jax.tree.map(lambda a, at=at: a[at],
                                 params["runs"][run])
            experts = jax.tree.map(lambda a, li=li: a[li], params["layers"])
            x, chosen = _layer(x, kind, layer, experts, c)
            choices.append(chosen)
            li, at = li + 1, at + 1
        x = _rms_norm(x, params["final_norm"], float(c.rms_eps))
        lg = (x @ embed.T) / float(c.logits_scaling)
    return lg, jnp.stack(choices)


def logits(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """Float32 logits ``[S, V]`` of one sequence of token ids ``[S]``."""
    return _forward(params, tokens, config)[0]


def router_choices(params: Dict[str, Any], tokens, config) -> jnp.ndarray:
    """The experts each position routed to, ``[L, S, k]`` int32, in the
    order of their logits; compare them as SETS."""
    return _forward(params, tokens, config)[1]


def gaps_and_choices(params: Dict[str, Any], prompt, chosen, config,
                     pad_to: int = 0):
    """One teacher-forced pass over ``prompt + chosen``: ``(gaps,
    choices)``. ``gaps``, as ``reference.chosen_gaps``: for each chosen
    token, how far its reference logit lies under the reference maximum
    at that position, in standard deviations of that position's logits.
    ``choices [L, len(prompt + chosen) - 1, k]``: the experts each
    position routed to. ``pad_to`` pads at the end (a causal model: what
    follows a position cannot change it)."""
    seq = (list(prompt) + list(chosen))[:-1]
    padded = seq + [0] * max(pad_to - len(seq), 0)
    lg, choices = _forward(params, padded, config)
    lg = lg[len(prompt) - 1:len(seq)]
    picked = jnp.take_along_axis(
        lg, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    gaps = (jnp.max(lg, axis=-1) - picked) / jnp.std(lg, axis=-1)
    return gaps, choices[:, :len(seq)]


def chosen_gaps(params: Dict[str, Any], prompt, chosen, config,
                pad_to: int = 0):
    """``reference.chosen_gaps`` for this family."""
    return gaps_and_choices(params, prompt, chosen, config, pad_to)[0]
