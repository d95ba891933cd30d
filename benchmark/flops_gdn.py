"""Operations and bytes the gated delta-rule state update of a decode
tick NEEDS, from shapes alone (``benchmark/flops.py``'s rule: what the
mathematics requires, nothing the program adds). ``config`` is a
configuration file's dict (Hugging Face key names, ``qwen3_next``).

One token of one sequence in one linear-attention layer advances ``Hv``
heads' states ``S [Dk, Dv]``: ``S = exp(g) S`` (1 operation an element),
``k^T S`` (2), ``S += k u^T`` (2) and ``q^T S`` (2): seven operations a
state element. It must read the state once and write it once (float32
here: the configuration file's ``assumed``), read ``q`` and ``k [Hk,
Dk]`` and ``v [Hv, Dv]`` in the model's dtype and ``g`` and ``beta
[Hv]`` in float32, and write ``o [Hv, Dv]``. Nothing is shared between
sequences, so a tick's least is that times every sequence whose state it
MUST advance: the LIVE ones. A kernel that also advances the free slots'
rows (as ``gdn_step`` does) reads that much lower, and a later one that
skips them cannot read over 100%.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds

STEP_OPS_PER_ELEMENT = 7.0


def state_elements(c: Dict[str, Any]) -> int:
    """One sequence's recurrent state in one layer."""
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"])


def linear_layers(c: Dict[str, Any]) -> int:
    """Layer ``i`` is linear unless ``(i + 1) % full_attention_interval
    == 0`` (as ``transformers`` derives ``layer_types``)."""
    return sum((i + 1) % c["full_attention_interval"] != 0
               for i in range(c["num_hidden_layers"]))


def step_flops(c: Dict[str, Any], sequences: float) -> float:
    return STEP_OPS_PER_ELEMENT * state_elements(c) * sequences


def step_bytes(c: Dict[str, Any], sequences: float, state_itemsize: int = 4,
               itemsize: int = 2) -> float:
    keys = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    values = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    rows = ((2 * keys + 2 * values) * itemsize            # q, k; v, o
            + 2 * c["linear_num_value_heads"] * 4)        # g, beta
    return sequences * (2.0 * state_elements(c) * state_itemsize + rows)


def tick_step_seconds(c: Dict[str, Any], sequences: float,
                      peak: Dict[str, Any]) -> float:
    """The least time one decode tick's state updates could take on a
    chip with ``peak``, for ``sequences`` live requests, in every
    linear-attention layer."""
    return linear_layers(c) * roofline_seconds(
        step_flops(c, sequences), step_bytes(c, sequences), peak)


def state_bytes(c: Dict[str, Any], sequences: float, state_itemsize: int = 4,
                itemsize: int = 2) -> float:
    """What ``sequences`` requests keep in every linear-attention layer:
    the recurrent state and the convolution's last ``K - 1`` inputs over
    the ``q | k | v`` channels."""
    channels = (2 * c["linear_num_key_heads"] * c["linear_key_head_dim"]
                + c["linear_num_value_heads"] * c["linear_value_head_dim"])
    tail = (c["linear_conv_kernel_dim"] - 1) * channels * itemsize
    return sequences * linear_layers(c) * (
        state_elements(c) * state_itemsize + tail)


def kv_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's K and V over the full-attention layers."""
    full = c["num_hidden_layers"] - linear_layers(c)
    return 2 * full * c["num_key_value_heads"] * c["head_dim"] * itemsize
