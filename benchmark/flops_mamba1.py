"""Operations and bytes a Jamba-family model (``model_type`` "jamba",
``num_experts`` 1: Mamba-1 mixers beside attention, a dense SwiGLU on
every layer) NEEDS, from shapes alone (``benchmark/flops.py``'s rule:
what the mathematics requires, nothing the program adds). ``config`` is
a configuration file's dict (Hugging Face key names).

THE SELECTIVE SCAN. One token of one sequence in one Mamba layer
advances a state ``s [Di, N]``: ``dt A`` (1 operation an element), its
``exp`` (counted apart: the transcendental unit's), ``s exp(.)`` (1),
``(dt u) B`` (1), their sum (1), ``s C`` and the sum over ``N`` (2): SIX
operations and ONE ``exp`` a state element. A tick must read the state
once and write it once (float32: the configuration file's ``assumed``),
read ``u`` in the model's dtype, ``dt`` in float32 and ``B``, ``C [N]``
in float32, write ``y`` in float32, and read ``A [Di, N]`` float32 once
a layer a call. Nothing is shared between sequences, so a tick's least
is that times every sequence whose state it MUST advance: the LIVE ones.
The prefill's scan moves the real tokens' ``u``, ``dt``, ``B``, ``C`` in
and ``y`` out, and a state a row: out once, in once where a chunk went
on from one.

THE WHOLE TICK (``tick_seconds``): the LARGER of its total operations
over the bf16 peak and its total bytes over HBM's rate: every matmul
weight once and 2 operations a weight a row, the live sequences' states
and conv tails in and out, the live tokens' K/V, the scan's operations.
A true lower bound: a share of it cannot read over 100.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds

STEP_OPS_PER_ELEMENT = 6.0


def inner(c: Dict[str, Any]) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def attn_layers(c: Dict[str, Any]) -> int:
    """Layer ``i`` is attention where ``i % attn_layer_period ==
    attn_layer_offset`` (``transformers``' ``layers_block_type``)."""
    return sum(i % c["attn_layer_period"] == c["attn_layer_offset"]
               for i in range(c["num_hidden_layers"]))


def mamba_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - attn_layers(c)


def state_elements(c: Dict[str, Any]) -> int:
    """One sequence's recurrent state in one layer."""
    return inner(c) * c["mamba_d_state"]


def step_flops(c: Dict[str, Any], tokens: float) -> float:
    return STEP_OPS_PER_ELEMENT * state_elements(c) * tokens


def token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """What one token of one layer's recurrence reads and writes beside
    the state: ``u`` (model dtype) and ``dt`` (float32) in, ``B`` and
    ``C`` (float32) in, ``y`` (float32) out."""
    return inner(c) * (itemsize + 4 + 4) + 2 * c["mamba_d_state"] * 4


def a_bytes(c: Dict[str, Any]) -> int:
    return 4 * state_elements(c)


def step_bytes(c: Dict[str, Any], sequences: float,
               state_itemsize: int = 4) -> float:
    """One layer's tick: every sequence's state once in and once out,
    its token's operands, ``A`` once a call."""
    return (sequences * (2.0 * state_elements(c) * state_itemsize
                         + token_bytes(c)) + a_bytes(c))


def tick_step_seconds(c: Dict[str, Any], sequences: float,
                      peak: Dict[str, Any]) -> float:
    """The least time one decode tick's state updates could take, for
    ``sequences`` live requests, in every Mamba layer."""
    return mamba_layers(c) * roofline_seconds(
        step_flops(c, sequences), step_bytes(c, sequences), peak)


def scan_bytes(c: Dict[str, Any], tokens: float, rows: float,
               carried: float) -> float:
    """One layer's prefill scan: ``tokens`` real tokens in ``rows`` rows
    of which ``carried`` started from a state."""
    return (tokens * token_bytes(c)
            + (rows + carried) * 4.0 * state_elements(c) + a_bytes(c))


def scan_seconds(c: Dict[str, Any], tokens: float, rows: float,
                 carried: float, peak: Dict[str, Any]) -> float:
    """The least time one ``cb_prefill`` call's scans could take, in
    every Mamba layer."""
    return mamba_layers(c) * roofline_seconds(
        step_flops(c, tokens), scan_bytes(c, tokens, rows, carried), peak)


def state_bytes(c: Dict[str, Any], sequences: float, state_itemsize: int = 4,
                itemsize: int = 2) -> float:
    """What ``sequences`` requests keep in every Mamba layer: the state
    and the convolution's last ``K - 1`` inputs."""
    tail = (c["mamba_d_conv"] - 1) * inner(c) * itemsize
    return sequences * mamba_layers(c) * (
        state_elements(c) * state_itemsize + tail)


def kv_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's K and V over the attention layers."""
    return (2 * attn_layers(c) * c["num_key_value_heads"] * c["head_dim"]
            * itemsize)


def matmul_params(c: Dict[str, Any]) -> int:
    """Weights that multiply every token: the mixers' projections, the
    SwiGLU of every layer and the (tied) head; the embedding is a
    lookup."""
    e, di = c["hidden_size"], inner(c)
    mamba = (e * 2 * di + di * (c["mamba_dt_rank"] + 2 * c["mamba_d_state"])
             + c["mamba_dt_rank"] * di + di * e)
    attention = e * c["head_dim"] * 2 * (c["num_attention_heads"]
                                         + c["num_key_value_heads"])
    return (mamba_layers(c) * mamba + attn_layers(c) * attention
            + c["num_hidden_layers"] * 3 * e * c["intermediate_size"]
            + e * c["vocab_size"])


def attn_flops(c: Dict[str, Any], live_tokens: float) -> float:
    """QK^T and PV of one tick over ``live_tokens`` keys in all, in
    every attention layer."""
    return (attn_layers(c) * 4.0 * c["num_attention_heads"] * c["head_dim"]
            * live_tokens)


def attn_bytes(c: Dict[str, Any], live_tokens: float, rows: float,
               itemsize: int = 2) -> float:
    """One tick's attention in every attention layer: the live tokens'
    K and V once, a query in and an output out a row."""
    return (live_tokens * kv_token_bytes(c, itemsize)
            + attn_layers(c) * rows * 2 * c["num_attention_heads"]
            * c["head_dim"] * itemsize)


def tick_attn_seconds(c: Dict[str, Any], live_tokens: float, rows: float,
                      peak: Dict[str, Any]) -> float:
    return roofline_seconds(attn_flops(c, live_tokens),
                            attn_bytes(c, live_tokens, rows), peak)


def tick_flops(c: Dict[str, Any], live_tokens: float, rows: float) -> float:
    return (2.0 * matmul_params(c) * rows
            + mamba_layers(c) * step_flops(c, rows)
            + attn_flops(c, live_tokens))


def tick_bytes(c: Dict[str, Any], live_tokens: float, rows: float,
               itemsize: int = 2) -> float:
    """One tick's least bytes: every matmul weight once, ``A`` once a
    Mamba layer, the live sequences' states and conv tails in and out,
    the live tokens' K/V."""
    return (matmul_params(c) * itemsize + mamba_layers(c) * a_bytes(c)
            + 2.0 * state_bytes(c, rows)
            + attn_bytes(c, live_tokens, rows, itemsize))


def tick_seconds(c: Dict[str, Any], live_tokens: float, rows: float,
                 peak: Dict[str, Any]) -> float:
    """The least time ONE decode tick could take for ``rows`` live
    sequences holding ``live_tokens`` tokens in all."""
    return roofline_seconds(tick_flops(c, live_tokens, rows),
                            tick_bytes(c, live_tokens, rows), peak)
