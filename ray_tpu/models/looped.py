"""A LOOPED layer stack (the Ouro family, ``LlamaConfig.loop_steps >
1``): one stack of LLaMA layers with four norms a layer, applied
``loop_steps`` times with the SAME weights. What is the family's own
lives here; the loop itself is ``for step in range(loop_steps)`` around
the layer scans of the engine's two forwards
(``continuous_batching._forward_paged``, ``_prefill_forward_paged``).

What the loop is, for the engine:

* the weights are stacked ``[L, ...]`` once; the K/V arena has ``loop_steps
  x L`` rows (``paged_kv.PagedKVCache.create``), because step ``t`` of
  layer ``l`` keeps its own keys and values, in row ``t x L + l``, and a
  step reads its own row alone. Block tables,
  the radix index and admission are a TOKEN's and know nothing of it;
* the final norm stands after EVERY step (between the steps, not only
  before the head: :func:`step_end`), and the exit gate is read after
  each (:func:`exit_gate`, one sigmoid a token a step). The published
  ``early_exit_threshold`` is 1, where every token takes every step:
  the gate is computed and fetched with the tick's row
  (:func:`gate_bits`; the host reads off it how many steps the program
  ran), never acted on;
* every layer is the plain attention kind, so no kernel knows of the
  loop: the paged kernels take the stacked arena and a row. A prefill's
  layers land their K/V in the arena as they go (stacked as the layer
  loop's output, the 192 rows of an 8 x 256 batch would be 3 GB beside
  an arena that fills the chip).

What a looped stack cannot have is the "looped" entry of
``continuous_batching._KIND_CANNOT``.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ray_tpu.models import llama


def step_scope(c, step: int):
    """The profiler scope of pass ``step`` of a looped stack; a model
    without a loop keeps the names it had."""
    if c.loop_steps == 1:
        return contextlib.nullcontext()
    return jax.named_scope(f"loop/step{step}")


def step_end(x, params, c):
    """What stands after the last layer of EVERY step: the final norm
    (its output is the next step's input, the gate's, and the head's).
    A model without a loop has one step."""
    return llama.norm(x, params["final_norm"], c)


def exit_gate(h, params):
    """``sigmoid(h . w_e + b_e)`` of a step's normed output ``h [B, S,
    E]``: float32 ``[B, S]``."""
    with jax.named_scope("loop/gate"):
        logit = jnp.einsum("bse,e->bs", h.astype(jnp.float32),
                           params["exit_gate_w"].astype(jnp.float32))
        return jax.nn.sigmoid(
            logit + params["exit_gate_b"].astype(jnp.float32))


def gate_bits(gates):
    """Every step's gates (a list of float32 ``[B, S]``) as the tick's
    row carries them behind its tokens: float32 bits as int32
    ``[T, B, S]``, so the host's one fetch a tick brings both."""
    return jax.lax.bitcast_convert_type(jnp.stack(gates), jnp.int32)
