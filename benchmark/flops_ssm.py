"""Operations and bytes the Mamba-2 state update of a decode tick NEEDS,
from shapes alone (``benchmark/flops.py``'s rule: what the mathematics
requires, nothing the program adds). ``config`` is a configuration
file's dict (Hugging Face key names).

One token of one sequence in one layer advances ``H`` heads' states
``h [P, N]``: ``h = exp(dt A) h + dt x B^T`` and ``y = h C``, five
operations a state element. It must read the state once and write it
once (float32 here: the configuration file's ``assumed``), read ``x [H,
P]``, ``B`` and ``C [G, N]`` and ``dt [H]``, and write ``y [H, P]``.
Nothing is shared between sequences, so a tick's least is that times
every sequence whose state it advances.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import roofline_seconds


def state_elements(c: Dict[str, Any]) -> int:
    """One sequence's recurrent state in one layer."""
    return c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]


def ssm_layers(c: Dict[str, Any]) -> int:
    return sum(kind == "mamba" for kind in c["layer_types"])


def step_flops(c: Dict[str, Any], sequences: float) -> float:
    return 5.0 * state_elements(c) * sequences


def step_bytes(c: Dict[str, Any], sequences: float, state_itemsize: int = 4,
               itemsize: int = 2) -> float:
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    rows = (2 * inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
            ) * itemsize + c["mamba_n_heads"] * 4        # x, y, B, C; dt
    return sequences * (2.0 * state_elements(c) * state_itemsize + rows)


def tick_step_seconds(c: Dict[str, Any], slots: int,
                      peak: Dict[str, Any]) -> float:
    """The least time one decode tick's state updates could take on a
    chip with ``peak``: the kernel advances EVERY slot's state, live or
    not (a freed slot's row is garbage nothing reads, but it is read and
    written), in every Mamba-2 layer."""
    return ssm_layers(c) * roofline_seconds(
        step_flops(c, slots), step_bytes(c, slots), peak)
