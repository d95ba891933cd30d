"""Operations and bytes a decode tick of a model with sliding-window
layers and a HELD SHARE of its routed experts NEEDS, from shapes alone
(``benchmark/flops.py``'s rule: what the mathematics requires, nothing
the program adds). ``config`` is a configuration file's dict (Hugging
Face key names, ``moe_intermediate_size`` for an expert's width).

Attention: one query a sequence a layer reads every key and value it
may see once: ``min(context, sliding_window)`` tokens in a
sliding-window layer, ``context`` in a full one, ``2 x kv_heads x
head_dim`` elements each, against ``4 x heads x head_dim`` FLOPs a
token seen. The blocks a paged cache rounds a context up to are the
program's, not needed; the counts below are given in TOKENS.

Routed experts: ``benchmark/flops_moe.py``'s count, for the assignments
that fall on experts held HERE and the held experts they touch, in the
routed layers alone (the leading dense layers have no experts).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds


def kv_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's K and V in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def layer_kinds(c: Dict[str, Any]):
    """(sliding-window layers, layers that keep every key)."""
    window = sum(k == "sliding_attention" for k in c["layer_types"])
    return window, len(c["layer_types"]) - window


def tick_attn_seconds(c: Dict[str, Any], window_tokens: float,
                      full_tokens: float, peak: Dict[str, Any],
                      itemsize: int = 2) -> float:
    """The least time one tick's attention could take: ``window_tokens``
    keys seen in EACH sliding-window layer and ``full_tokens`` in each
    other layer, summed over the tick's sequences."""
    n_window, n_full = layer_kinds(c)
    tokens = n_window * window_tokens + n_full * full_tokens
    flops = 4.0 * c["num_attention_heads"] * c["head_dim"] * tokens
    return roofline_seconds(flops, tokens * kv_token_bytes(c, itemsize),
                            peak)


def expert_params(c: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def routed_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def tick_gmm_seconds(c: Dict[str, Any], local_assignments: float,
                     touched_experts: float, peak: Dict[str, Any],
                     itemsize: int = 2) -> float:
    """The least time one tick's grouped multiplications could take:
    ``local_assignments`` (token, held expert) pairs a routed layer over
    ``touched_experts`` of its held experts: each touched expert's three
    matrices once, the routed rows in and out, against ``2 x
    expert_params`` FLOPs an assignment; every routed layer."""
    flops = 2.0 * expert_params(c) * local_assignments
    rows = 2.0 * local_assignments * c["hidden_size"] * itemsize
    return routed_layers(c) * roofline_seconds(
        flops, touched_experts * expert_params(c) * itemsize + rows, peak)
