"""Operations and bytes the EVA attention of a decode tick NEEDS, and
those of pooling a window into its summaries, from shapes alone
(``benchmark/flops.py``'s rule: what the mathematics requires, nothing
the program adds). ``config`` is a configuration file's dict (Hugging
Face key names, ``evabyte``: ``window_size``, ``chunk_size``; a head is
``hidden_size / num_attention_heads`` wide).

A decode query of one sequence in one layer reads every key it may see
once, and its value: ``S = window_size / chunk_size`` summaries for each
closed window and the raw keys of the open one, its own included. K and
V are ``num_key_value_heads x head`` each, in bf16. It scores each key
(2 operations a head dim) and weighs each value (2 more), over
``num_attention_heads``. Nothing is shared between sequences or between
a key head's queries (MHA: one query a key head), so the tick sits at
about 1 operation a byte: HBM-bound by two orders of magnitude. The
queries in and the outputs out are counted too. Whole blocks that a
program streams past a context's end are the program's, not needed.

Pooling a window (``summarise``): every key and value of the window read
once, the pooling logit (2 operations a dim), the softmax over a chunk,
two weighted sums (2 each), and ``S`` pooled pairs written.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def summaries_per_window(c: Dict[str, Any]) -> int:
    return c["window_size"] // c["chunk_size"]


def key_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """One key AND its value, one layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * itemsize


def tick_attn_bytes(c: Dict[str, Any], keys: float, slots: float,
                    itemsize: int = 2) -> float:
    ends = 2 * slots * c["num_attention_heads"] * head_dim(c) * itemsize
    return c["num_hidden_layers"] * (keys * key_bytes(c, itemsize) + ends)


def tick_attn_flops(c: Dict[str, Any], keys: float) -> float:
    return (4.0 * c["num_attention_heads"] * head_dim(c) * keys
            * c["num_hidden_layers"])


def tick_attn_seconds(c: Dict[str, Any], keys: float, slots: float,
                      peak: Dict[str, Any]) -> float:
    """The least time one tick's attention could take: ``keys`` keys
    attended in EACH layer (summaries and raw keys alike), summed over
    the tick's ``slots`` live sequences."""
    return roofline_seconds(tick_attn_flops(c, keys),
                            tick_attn_bytes(c, keys, slots), peak)


def summarise_bytes(c: Dict[str, Any], windows: float,
                    itemsize: int = 2) -> float:
    per_window = (c["window_size"] + summaries_per_window(c)) * key_bytes(
        c, itemsize)
    return c["num_hidden_layers"] * windows * per_window


def summarise_flops(c: Dict[str, Any], windows: float) -> float:
    per_key = 6.0 * c["num_key_value_heads"] * head_dim(c)
    return c["num_hidden_layers"] * windows * c["window_size"] * per_key


def summarise_seconds(c: Dict[str, Any], windows: float,
                      peak: Dict[str, Any]) -> float:
    return roofline_seconds(summarise_flops(c, windows),
                            summarise_bytes(c, windows), peak)
