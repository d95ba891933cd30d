"""Operations and bytes the routed expert block's grouped matrix
multiplications NEED, from shapes alone (``benchmark/flops.py``'s rule:
what the mathematics requires, nothing the program adds). ``config`` is
a configuration file's dict (Hugging Face key names); an ASSIGNMENT is
one (token, expert) pair, ``num_experts_per_tok`` of them a token.

One layer's block is three multiplications per assignment, gate and up
``[hidden] x [hidden, width]`` and down ``[width] x [width, hidden]``:
``6 x hidden x width`` FLOPs. It must read each TOUCHED expert's three
matrices once, the routed rows once, and write their outputs once; the
``[assignments, width]`` activations between the multiplications need
not leave the chip, and an untouched expert's weights are never needed.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds


def expert_params(c: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def gmm_flops(c: Dict[str, Any], assignments: float) -> float:
    return 2.0 * expert_params(c) * assignments


def gmm_bytes(c: Dict[str, Any], assignments: float, touched_experts: float,
              itemsize: int = 2) -> float:
    rows = 2.0 * assignments * c["hidden_size"] * itemsize    # in and out
    return touched_experts * expert_params(c) * itemsize + rows


def tick_gmm_seconds(c: Dict[str, Any], slots: int, touched_share: float,
                     peak: Dict[str, Any], itemsize: int = 2) -> float:
    """The least time one decode tick's grouped multiplications could
    take on a chip with ``peak``: every slot routes (live or not), so
    ``slots x top-k`` assignments a layer over ``touched_share`` (0-1) of
    the layer's experts, all layers."""
    assignments = slots * c["num_experts_per_tok"]
    return c["num_hidden_layers"] * roofline_seconds(
        gmm_flops(c, assignments),
        gmm_bytes(c, assignments, touched_share * c["num_experts"], itemsize),
        peak)
