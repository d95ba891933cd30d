"""A LOOPED layer stack in the continuous-batching engine (the Ouro
family, ``LlamaConfig.loop_steps > 1``): one stack of LLaMA layers with
four norms a layer, applied ``loop_steps`` times with the SAME weights.

What the loop is, for the engine:

* the weights are stacked ``[L, ...]`` once; the K/V arena has ``loop_steps
  x L`` rows (``paged_kv.PagedKVCache.create``), because step ``t`` of
  layer ``l`` keeps its own keys and values, in row ``t x L + l``, and a
  step reads its own row alone. Block tables,
  the radix index and admission are a TOKEN's and know nothing of it;
* both engine programs run the layer scan ``loop_steps`` times
  (:func:`forward_paged`, the tick; :func:`prefill_forward`), the final
  norm after EVERY step (it stands between the steps, not only before
  the head: :func:`step_end`), the exit gate read after each
  (:func:`exit_gate`, one sigmoid a token a step). The published
  ``early_exit_threshold`` is 1, where every token takes every step:
  the gate is computed and fetched with the tick's row (the host reads
  off it how many steps the program ran), never acted on;
* every layer is the plain attention kind, so the layer is written with
  the engine's own pieces (``_layer_qkv``, ``_write_then_attend``,
  ``_attn_out``, ``_layer_finish``) and no kernel knows of the loop: the
  paged kernels take the stacked arena and a row.

Why a module of its own: ``continuous_batching``'s programs of every
model WITHOUT a loop must lower to the text they always did, source
positions included (a Mosaic kernel's serialized body carries the
positions of the frames it was traced under, and it is part of the
compile-cache key). So nothing was added to those functions: the
engine's constructor asks :func:`install` for the two programs and the
host's accounting when ``loop_steps > 1``, and :func:`refuse` for what
a looped stack cannot have (:data:`LOOP_CANNOT`, the table beside
``continuous_batching._KIND_CANNOT``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu._private import xla_monitor
from ray_tpu.models import continuous_batching as cb
from ray_tpu.models import llama
from ray_tpu.models.inference import _attend_cached, lm_head_logits
from ray_tpu.models.paged_kv import GARBAGE_BLOCK, PagedKVCache
from ray_tpu.ops.attention import paged_chunk_attention

# What a looped stack cannot have, or what cannot run one, and why: by
# the name the caller used (an engine capability as ``_KIND_CANNOT``
# spells it, or a service outside the engine). Each either runs every
# step or raises from here; none runs one pass of the stack silently,
# which would be another model's output under this model's name.
LOOP_CANNOT = {
    "kv_dtype": "the arena's rows are held to the float32 reference as bf16 "
                "only: an 8-bit arena under four passes of the stack is a "
                "different model output until it is measured",
    "speculative": "the self-draft runs the first layers of ONE pass, which "
                   "is no shallow copy of a model that applies its stack "
                   "loop_steps times, and the verify programs run one pass",
    "handoff": "the KV handoff sizes and checks its payload by num_layers, "
               "and a looped arena has loop_steps x num_layers rows",
    "score_logprobs": "it runs llama.forward, the training forward, which "
                      "does not run the loop",
    "llama.forward": "the training forward (and loss_fn on it) runs the "
                     "stack once; the loop's objective also needs a weight "
                     "on the exit entropy that the published config does "
                     "not give",
    "LlamaGenerator": "the dense-cache generator keeps num_layers cache rows "
                      "and runs the stack once",
    "ExternalLlamaDrafter": "a drafter's dense cache keeps num_layers rows "
                            "and its forward runs the stack once",
}


def refuse(asked: Dict[str, str]) -> None:
    """Raise for the first capability in ``asked`` ({capability: what the
    caller called it}, as ``continuous_batching._refuse_unsupported``
    takes it) that a looped stack cannot have."""
    for capability, called in asked.items():
        if capability in LOOP_CANNOT:
            raise ValueError(
                f"{called} is not supported for a model with a looped layer "
                f"stack (loop_steps > 1): {LOOP_CANNOT[capability]}")


def refuse_service(config: llama.LlamaConfig, service: str) -> None:
    """``service`` (a name of :data:`LOOP_CANNOT`) does not run a looped
    stack: raise, naming it, for a config that has one."""
    if config.loop_steps > 1:
        raise NotImplementedError(
            f"{service} does not run a model with a looped layer stack "
            f"(loop_steps = {config.loop_steps}): {LOOP_CANNOT[service]}; "
            "the continuous-batching engine serves it")


def step_end(x, params, c):
    """What stands after the last layer of EVERY step: the final norm
    (its output is the next step's input, the gate's, and the head's)."""
    return llama.norm(x, params["final_norm"], c)


def exit_gate(h, params):
    """``sigmoid(h . w_e + b_e)`` of a step's normed output ``h [B, S,
    E]``: float32 ``[B, S]``."""
    logit = jnp.einsum("bse,e->bs", h.astype(jnp.float32),
                       params["exit_gate_w"].astype(jnp.float32))
    return jax.nn.sigmoid(logit + params["exit_gate_b"].astype(jnp.float32))


def forward_paged(params, tokens, positions, tables, limits,
                  cache: PagedKVCache, config: llama.LlamaConfig,
                  use_kernel: bool):
    """``continuous_batching._forward_paged`` for a looped stack: each
    slot's window of S tokens ``[B, S]`` at ``positions [B, S]`` through
    ``loop_steps`` passes of the stack, pass ``t`` writing and attending
    rows ``t x L ..`` of the arena. Returns (float32 logits ``[B, S, V]``
    of the LAST step, the arena, the gates ``[T, B, S]``)."""
    c = config
    bs = cache.block_size
    cos, sin = cb._rope_tables(c, 0, positions)
    x = cb._embed(params, tokens, c)
    gathered = jnp.take_along_axis(tables, positions // bs, axis=1)
    block_idx = jnp.where(positions < limits[:, None], gathered,
                          GARBAGE_BLOCK)
    offset = positions % bs
    visits = cb._window_visits(tables, positions, limits, cache.k, use_kernel)
    scanned, _ = llama.split_layers(params)

    def layer_fn(carry, layer, first_row):
        x, arenas, li = carry
        q, k, v, gate = cb._layer_qkv(x, layer, cos, sin, c)
        o, arenas = cb._write_then_attend(
            arenas, li + first_row, q, k, v, block_idx, offset, tables,
            positions, visits, c.attn_scale, use_kernel)
        mixed = cb._attn_out(o.astype(x.dtype), layer, c, gate)
        x, _, _ = cb._layer_finish(x, mixed, layer, c, None, li, use_kernel)
        return (x, arenas, li + 1), None

    arenas, gates = tuple(cache), []
    for step in range(c.loop_steps):
        with jax.named_scope(f"loop/step{step}"):
            (x, arenas, _), _ = jax.lax.scan(
                functools.partial(layer_fn, first_row=step * c.num_layers),
                (x, arenas, jnp.int32(0)), scanned)
            x = step_end(x, params, c)
        with jax.named_scope("loop/gate"):
            gates.append(exit_gate(x, params))
    return (lm_head_logits(x, params, c), type(cache)(*arenas),
            jnp.stack(gates))


def decode_tick(params, tokens, positions, tables, limits, cache, step,
                config: llama.LlamaConfig, use_kernel: bool, sampling):
    """``continuous_batching._decode_tick_paged`` for a looped stack.
    The row to fetch carries, behind the ``[B]`` tokens, every slot's
    gate of every step (float32 bits as int32, ``[T, B]``): the host's
    one fetch a tick brings both."""
    logits, cache, gates = forward_paged(
        params, tokens[:, None], positions[:, None], tables, limits, cache,
        config, use_kernel)
    next_tokens = cb._next_tokens(logits, step, sampling)
    bits = jax.lax.bitcast_convert_type(gates[:, :, 0], jnp.int32)
    return (next_tokens, positions + 1, cache, step + 1,
            jnp.concatenate([next_tokens, bits.reshape(-1)]))


def prefill_forward(params, tokens, positions, cache: PagedKVCache, ptables,
                    tables_w, last_idx, config: llama.LlamaConfig):
    """A prefill (or one chunk of it) of a looped stack: ``tokens [N, S]``
    at ``positions [S]`` behind each row's ``m`` earlier blocks
    (``ptables [N, m]``: a matched prefix, or the prompt's earlier
    chunks), ``loop_steps`` passes; in pass ``t`` layer ``l`` attends
    the earlier keys of ROW ``t x L + l`` and its own, and lands its K/V
    in that row through ``tables_w [N, S / bs]`` as it goes: the arena
    rides the layer loop's carry (stacked as the loop's output, 192
    rows of an 8 x 256 batch would be 3 GB beside an arena that fills
    the chip). Under ``PREFILL_DENSE_KEYS`` keys the scores are made at
    once in float32 (``inference._attend_cached``, over the gathered
    earlier keys), over it blockwise where the keys lie
    (``paged_chunk_attention``): the engine's two prefill forms.
    Returns (logits ``[N, 1, V]`` at ``last_idx``, the arena)."""
    c = config
    bs = cache.block_size
    n, s_pad = tokens.shape
    m = ptables.shape[1]
    dense = m * bs + s_pad <= cb.PREFILL_DENSE_KEYS
    cos, sin = cb._rope_tables(c, s_pad, positions)
    x = cb._embed(params, tokens, c)
    scanned, _ = llama.split_layers(params)
    flat_p, flat_w = ptables.reshape(-1), tables_w.reshape(-1)

    def layer_fn(carry, layer, first_row):
        x, arenas, li = carry
        row = li + first_row
        q, k, v, gate = cb._layer_qkv(x, layer, cos, sin, c)
        if not dense:
            o = paged_chunk_attention(q, k, v, arenas[0], arenas[1], row,
                                      ptables, 0, m * bs, c.attn_scale)
        else:
            ck, cv = k, v
            if m:       # the earlier keys as attention reads them
                earlier = [cb._blocks_to_ctx(a[row, flat_p][None], n)[0]
                           for a in arenas]
                ck = jnp.concatenate([earlier[0].astype(c.dtype), k], axis=1)
                cv = jnp.concatenate([earlier[1].astype(c.dtype), v], axis=1)
            o = _attend_cached(q, ck, cv, positions, c.attn_scale)
        mixed = cb._attn_out(o, layer, c, gate)
        arenas = tuple(
            a.at[row, flat_w].set(
                cb._ctx_to_blocks(new[None].astype(a.dtype), bs)[0])
            for a, new in zip(arenas, (k, v)))
        x, _, _ = cb._layer_finish(x, mixed, layer, c, None, li)
        return (x, arenas, li + 1), None

    arenas = (cache.k, cache.v)     # bf16: a looped arena has no scales
    for step in range(c.loop_steps):
        with jax.named_scope(f"loop/step{step}"):
            (x, arenas, _), _ = jax.lax.scan(
                functools.partial(layer_fn, first_row=step * c.num_layers),
                (x, arenas, jnp.int32(0)), scanned)
            if step < c.loop_steps - 1:
                x = step_end(x, params, c)
    # The head reads one position a row: the last step's norm is taken
    # there alone.
    x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)  # [N, 1, E]
    x = step_end(x, params, c)
    return lm_head_logits(x, params, c), PagedKVCache(*arenas)


def _book_steps(eng, account, tick_fn, tick: Dict[str, Any], k: int) -> None:
    """A landed tick's live rows and the steps the program ran for them
    (read off the ROW: the gates it carries), before the engine's own
    accounting of it (``ContinuousBatcher._account_tick``)."""
    from ray_tpu._private import metrics_defs as mdefs

    live = len(tick["members"])
    steps = (len(tick["row"]) - eng.num_slots) // eng.num_slots
    mdefs.CB_LOOP_ROWS.inc(live, tags=eng._mtags)
    mdefs.CB_LOOP_STEPS.inc(live * steps, tags=eng._mtags)
    account(tick_fn, tick, k)


def install(eng) -> None:
    """Give a ``ContinuousBatcher`` whose config has ``loop_steps > 1``
    its two programs (under the names, shape policies and donations of
    the ones it has) and its accounting: called last in its constructor,
    so that the constructor itself stays what it was for every other
    model."""
    from ray_tpu._private import metrics_defs as mdefs

    cfg, use_kernel, sampling = eng.config, eng.use_decode_kernel, eng.sampling
    block_size = eng.block_size

    @xla_monitor.instrument(name="cb_prefill", shape_policy="bucketed",
                            allowed_dims=tuple(eng._prefill.allowed_dims),
                            donate_argnums=(2,))
    def prefill(params, tokens, cache, ptables, tables_w, last_idx, pstep,
                slots=None):
        positions = ptables.shape[1] * block_size + jnp.arange(tokens.shape[1])
        logits, cache = prefill_forward(params, tokens, positions, cache,
                                        ptables, tables_w, last_idx, cfg)
        return cb._next_tokens(logits, pstep, sampling,
                               salt=cb._PREFILL_SALT), cache

    @xla_monitor.instrument(name="cb_tick", donate_argnums=(5,))
    def tick(params, tokens, positions, tables, limits, cache, step):
        return decode_tick(params, tokens, positions, tables, limits, cache,
                           step, cfg, use_kernel, sampling)

    eng._prefill, eng._tick = prefill, tick
    eng._account_tick = functools.partial(_book_steps, eng, eng._account_tick)
    once = eng.tick_bytes_estimate      # every weight once: one pass's bytes
    eng.tick_bytes_estimate = lambda **kw: once(**kw) + (
        cfg.loop_steps - 1) * eng._layer_param_bytes
    mdefs.CB_LOOP_KV_BYTES.set(eng.cache.k.nbytes + eng.cache.v.nbytes,
                               tags=eng._mtags)
