"""Operations and bytes the ALGORITHM needs, from shapes alone.

The yardstick's arithmetic: utilisation and roofline shares divide these
by measured time, so they count what the mathematics requires and
nothing the program chooses to add (recomputation under remat, logits of
positions nobody samples, padding to a bucket). ``config`` is a
configuration file's dict (Hugging Face key names). Copied in idea from
``bench.py::_train_flops``, with the embedding lookup no longer counted
as a matrix multiplication.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_matmul_params(c: Dict[str, Any]) -> int:
    e, d = c["hidden_size"], c["head_dim"]
    attn = e * d * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])
    return attn + 3 * e * c["intermediate_size"]


def matmul_params(c: Dict[str, Any]) -> int:
    """Weights that multiply every token: the layers and the output
    head (the embedding table is a lookup)."""
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def held_params(c: Dict[str, Any]) -> int:
    norms = c["hidden_size"] * (2 * c["num_hidden_layers"] + 1)
    return matmul_params(c) + c["hidden_size"] * c["vocab_size"] + norms


def attention_flops(c: Dict[str, Any], queries: int, keys: float) -> float:
    """QK^T and PV for ``queries`` positions that each see ``keys`` keys
    on average, one layer, forward."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * queries * keys


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward, causal: 6 per weight, and attention at an
    average of ``seq_len / 2`` keys, three times its forward."""
    attn = 3 * c["num_hidden_layers"] * attention_flops(c, 1, seq_len / 2)
    return 6.0 * matmul_params(c) + attn


def prefill_flops(c: Dict[str, Any], prompt_len: int) -> float:
    """One prompt: every position through the layers, causal attention,
    and the head at the LAST position only."""
    layers = c["num_hidden_layers"] * (
        2.0 * layer_matmul_params(c) * prompt_len
        + attention_flops(c, prompt_len, prompt_len / 2))
    return layers + 2.0 * c["hidden_size"] * c["vocab_size"]


def kv_bytes_per_token(c: Dict[str, Any], kv_itemsize: int = 2) -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * kv_itemsize)


def decode_tick_bytes(c: Dict[str, Any], live_tokens: int,
                      weight_itemsize: int = 2, kv_itemsize: int = 2) -> float:
    """The least one decode tick reads: every matmul weight once, and
    the keys and values of every live token once."""
    return (matmul_params(c) * weight_itemsize
            + live_tokens * kv_bytes_per_token(c, kv_itemsize))


def decode_tick_flops(c: Dict[str, Any], slots: int, live_tokens: int) -> float:
    return (2.0 * matmul_params(c) * slots
            + c["num_hidden_layers"] * attention_flops(c, 1, live_tokens))


def paged_decode_attention_bytes(c: Dict[str, Any], live_tokens: int,
                                 kv_itemsize: int = 2) -> float:
    """One layer's paged decode-attention call: K and V of the live
    tokens (queries and outputs are noise beside them)."""
    return (2.0 * c["num_key_value_heads"] * c["head_dim"] * kv_itemsize
            * live_tokens)


def roofline_seconds(flops: float, bytes_: float, peak: Dict[str, Any]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
