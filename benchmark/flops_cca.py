"""Operations and bytes a decode tick of a model with Compressed
Convolutional Attention layers and a top-1 routed expert layer NEEDS,
from shapes alone (``benchmark/flops.py``'s rule: what the mathematics
requires, nothing the program adds). ``config`` is a configuration
file's dict (Hugging Face key names, ``moe_intermediate_size`` for an
expert's width).

Attention: one query a sequence a layer reads every key and value it
may see once, IN THE LATENT: ``2 x kv_heads x head_dim`` elements a
token a layer (1,024 B as published, where per-head K and V at the
hidden width would be ``2 x hidden_size``: 8,192 B), against ``4 x heads
x head_dim`` FLOPs a token seen, plus each row's query in and output
out. The blocks a paged cache rounds a context up to are the program's,
not needed; the counts below are given in TOKENS.

Routed experts: each TOUCHED expert's three matrices once (25.2 MB as
published), the routed rows in and out, ``2 x expert_params`` FLOPs an
assignment; every layer is routed.

The tail a slot keeps beside its K/V (the two convolutions' last inputs
and the shifted value half, 2,688 values a layer as published) is read
and written once a tick by XLA's own code, no kernel: nothing here
counts it (the gauge ``ray_tpu_cb_cca_tail_bytes`` books it, in the
result line's ``detail``).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds


def kv_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's K and V in one layer, in the latent."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def tick_attn_seconds(c: Dict[str, Any], tokens: float, rows: float,
                      peak: Dict[str, Any], itemsize: int = 2) -> float:
    """The least time one tick's attention could take: ``tokens`` keys
    seen in EACH layer, summed over the tick's ``rows`` sequences."""
    layers = c["num_hidden_layers"]
    width = c["num_attention_heads"] * c["head_dim"]
    flops = 4.0 * width * tokens * layers
    moved = (tokens * kv_token_bytes(c, itemsize)
             + 2.0 * rows * width * itemsize) * layers
    return roofline_seconds(flops, moved, peak)


def expert_params(c: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def tick_gmm_seconds(c: Dict[str, Any], assignments: float,
                     touched_experts: float, peak: Dict[str, Any],
                     itemsize: int = 2) -> float:
    """The least time one tick's grouped multiplications could take:
    ``assignments`` (token, expert) pairs a layer over
    ``touched_experts`` of its experts; every layer."""
    flops = 2.0 * expert_params(c) * assignments
    rows = 2.0 * assignments * c["hidden_size"] * itemsize
    return c["num_hidden_layers"] * roofline_seconds(
        flops, touched_experts * expert_params(c) * itemsize + rows, peak)
