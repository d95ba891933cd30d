"""Operations and bytes a decode tick of a model with LATENT attention
(MLA) and a HELD SHARE of its routed experts NEEDS, from shapes alone
(``benchmark/flops.py``'s rule: what the mathematics requires, nothing
the program adds). ``config`` is a configuration file's dict (Hugging
Face key names; ``n_routed_experts`` is the experts HELD here).

Latent attention in absorbed form: one query a head a sequence a layer
reads each cache row it may see once, ``kv_lora_rank + qk_rope_head_dim``
elements (576 for Kimi K2: 1152 B in bf16; the lanes a program pads a row
with are the program's, not needed), scores it over all of them and
weighs its first ``kv_lora_rank``: ``2 x heads x (576 + 512)`` FLOPs a
row seen. The heads' absorbed queries in and latent outputs out are
counted too. One query row a slot sits at 121 FLOP/B, half of a v5e's
ridge, so both terms matter and the larger wins.

Routed experts: ``benchmark/flops_moe.py``'s count, for the assignments
that fall on experts held HERE and the held experts they touch, in the
routed layers alone (the first ``first_k_dense_replace`` have none).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops_window
from benchmark.flops import roofline_seconds


def latent_row(c: Dict[str, Any]) -> int:
    """Values one token keeps in one layer."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def latent_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's cache row in one layer."""
    return latent_row(c) * itemsize


def per_head_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """What per-head K and V of the same token would take in one layer."""
    return c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    ) * itemsize


def tick_attn_bytes(c: Dict[str, Any], live_tokens: float, slots: int,
                    itemsize: int = 2) -> float:
    rows = live_tokens * latent_token_bytes(c, itemsize)
    ends = slots * c["num_attention_heads"] * (
        latent_row(c) + c["kv_lora_rank"]) * itemsize    # q in, o out
    return c["num_hidden_layers"] * (rows + ends)


def tick_attn_flops(c: Dict[str, Any], live_tokens: float) -> float:
    return (2.0 * c["num_attention_heads"] * live_tokens
            * (latent_row(c) + c["kv_lora_rank"]) * c["num_hidden_layers"])


def tick_attn_seconds(c: Dict[str, Any], live_tokens: float, slots: int,
                      peak: Dict[str, Any], itemsize: int = 2) -> float:
    """The least time one tick's latent attention could take:
    ``live_tokens`` cache rows seen in EACH layer, summed over the
    tick's ``slots`` sequences."""
    return roofline_seconds(tick_attn_flops(c, live_tokens),
                            tick_attn_bytes(c, live_tokens, slots, itemsize),
                            peak)


def _as_window(c: Dict[str, Any]) -> Dict[str, Any]:
    """``c`` under the key ``benchmark/flops_window.py`` counts leading
    dense layers by."""
    return dict(c, num_dense_layers=c["first_k_dense_replace"])


expert_params = flops_window.expert_params


def routed_layers(c: Dict[str, Any]) -> int:
    return flops_window.routed_layers(_as_window(c))


def tick_gmm_seconds(c: Dict[str, Any], local_assignments: float,
                     touched_experts: float, peak: Dict[str, Any],
                     itemsize: int = 2) -> float:
    """``flops_window.tick_gmm_seconds``: the least time one tick's
    grouped multiplications could take, ``local_assignments`` (token,
    held expert) pairs a routed layer over ``touched_experts`` of its
    held experts, every routed layer."""
    return flops_window.tick_gmm_seconds(
        _as_window(c), local_assignments, touched_experts, peak, itemsize)
