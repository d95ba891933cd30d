"""Operations and bytes a decode tick of a LOOPED stack NEEDS, from
shapes alone (``benchmark/flops.py``'s rule: what the mathematics
requires, nothing the program adds). ``config`` is a
configuration file's dict (Hugging Face key names, ``ouro``:
``total_ut_steps`` passes of ``num_hidden_layers`` layers with the same
weights).

A tick: the layers' matmul weights are read once a STEP (the chip's 128
MiB of CMEM holds a twentieth of them, so a pass cannot keep them for
the next), the head once; every live token's K and V of every (step,
layer) pair once, ``total_ut_steps x num_hidden_layers x 2 x
num_key_value_heads x head_dim`` values; the queries in and the outputs
out of every (step, layer)'s attention. The embedding is a lookup, the
norms and the gate are kilobytes. Whole blocks that a program streams
past a context's end, and the padding of a bucket, are the program's,
not needed.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import flops
from benchmark.flops import roofline_seconds


def steps(c: Dict[str, Any]) -> int:
    return int(c["total_ut_steps"])


def kv_token_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one token in every (step, layer) pair."""
    return steps(c) * flops.kv_bytes_per_token(c, itemsize)


def stack_weight_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """The matmul weights of one pass of the stack."""
    return c["num_hidden_layers"] * flops.layer_matmul_params(c) * itemsize


def head_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    return c["hidden_size"] * c["vocab_size"] * itemsize


def tick_attn_bytes(c: Dict[str, Any], live_tokens: float, rows: float,
                    itemsize: int = 2) -> float:
    """The paged attention calls of one tick (one a (step, layer) pair):
    the live tokens' K and V, queries in and outputs out."""
    ends = (2 * rows * c["num_attention_heads"] * c["head_dim"] * itemsize
            * steps(c) * c["num_hidden_layers"])
    return live_tokens * kv_token_bytes(c, itemsize) + ends


def tick_attn_flops(c: Dict[str, Any], live_tokens: float) -> float:
    return (steps(c) * c["num_hidden_layers"]
            * flops.attention_flops(c, 1, live_tokens))


def tick_attn_seconds(c: Dict[str, Any], live_tokens: float, rows: float,
                      peak: Dict[str, Any]) -> float:
    return roofline_seconds(tick_attn_flops(c, live_tokens),
                            tick_attn_bytes(c, live_tokens, rows), peak)


def tick_bytes(c: Dict[str, Any], live_tokens: float, rows: float,
               itemsize: int = 2) -> float:
    """The least one decode tick reads and writes."""
    return (steps(c) * stack_weight_bytes(c, itemsize) + head_bytes(c, itemsize)
            + tick_attn_bytes(c, live_tokens, rows, itemsize))


def tick_flops(c: Dict[str, Any], live_tokens: float, rows: float) -> float:
    weights = (steps(c) * c["num_hidden_layers"] * flops.layer_matmul_params(c)
               + c["hidden_size"] * c["vocab_size"])
    return 2.0 * weights * rows + tick_attn_flops(c, live_tokens)


def tick_seconds(c: Dict[str, Any], live_tokens: float, rows: float,
                 peak: Dict[str, Any]) -> float:
    """The least time ONE tick could take: ``rows`` live sequences
    holding ``live_tokens`` tokens between them."""
    return roofline_seconds(tick_flops(c, live_tokens, rows),
                            tick_bytes(c, live_tokens, rows), peak)

