"""The prefill scan kernel's share of its roofline, in percent: the least
time ONE ``cb_prefill`` call's gated delta-rule scans could take over the
``gdn_chunk_scan`` kernel's measured own time a call of ``program``
(the by-kernel part of the trace reduction,
``runners/serve_moe.py::by_kernel``).

The least, from shapes and the registry alone (``benchmark/flops.py``'s
rule: what the mathematics requires). A call's REAL prompt tokens
(``ray_tpu_cb_prefill_tokens_total`` over the calls,
``ray_tpu_cb_prefill_chunk_ms_count``), in every Gated DeltaNet layer:
``q`` and ``k`` once a KEY head and ``v`` in and ``o`` out in the
model's dtype, ``g`` and ``beta`` in float32 (``flops_gdn.step_bytes``
less its state); the float32 state written once a row a call
(``ray_tpu_cb_state_installs_total`` +
``ray_tpu_cb_prefill_state_carries_total``) and read once where a chunk
started from one (the carries); 7 operations a state element a token
(``flops_gdn.STEP_OPS_PER_ELEMENT``: the recurrence's own count, which
the chunked form's matrix products exceed). Through
``flops.roofline_seconds`` and ``peaks.json``: HBM-bound at these
shapes.

It counts real tokens and the kernel runs the padded ones too, so it
cannot read over 100. The sixteen chunks of a row are a SEQUENCE (each
starts from the state the last one left), and inside a chunk the solve
is a chain of dependent steps, which keeps the kernel well under 100 at
any row count: its ceiling is not known.

A trace without the by-kernel part, a program that never ran the kernel
(the parent of PR 44) or books none of these series, or a configuration
without ``linear_num_value_heads`` reads nothing.
"""

from typing import Optional

from benchmark import flops_gdn, peaks
from benchmark.flops import roofline_seconds

TOKENS = "ray_tpu_cb_prefill_tokens_total"
CALLS = "ray_tpu_cb_prefill_chunk_ms_count"
INSTALLS = "ray_tpu_cb_state_installs_total"
CARRIES = "ray_tpu_cb_prefill_state_carries_total"


def _delta(ctx, name: str) -> float:
    return (ctx["registry_after"].get(name, 0.0)
            - ctx["registry_before"].get(name, 0.0))


def least_seconds(config, tokens: float, rows: float, carried: float,
                  peak) -> float:
    """One call's scans in every linear layer: ``tokens`` real tokens in
    ``rows`` rows of which ``carried`` started from a state."""
    state = 4.0 * flops_gdn.state_elements(config)
    return flops_gdn.linear_layers(config) * roofline_seconds(
        flops_gdn.step_flops(config, tokens),
        flops_gdn.step_bytes(config, tokens, state_itemsize=0)
        + (rows + carried) * state, peak)


def read(ctx, kernel: str, program: str) -> Optional[float]:
    config = ctx.get("config") or {}
    if (not config.get("linear_num_value_heads")
            or not ctx.get("registry_before")
            or not ctx.get("registry_after")):
        return None
    trace = ctx.get("trace") or {}
    by_program = (trace.get("kernels") or {}).get(kernel) or {}
    traced = (trace.get("programs") or {}).get(program, (0.0, 0))[1]
    calls = _delta(ctx, CALLS)
    if not traced or program not in by_program or calls <= 0:
        return None
    carried = _delta(ctx, CARRIES)
    least = least_seconds(
        config, _delta(ctx, TOKENS) / calls,
        (carried + _delta(ctx, INSTALLS)) / calls, carried / calls,
        peaks.for_device(ctx["device"]["kind"]))
    return 100.0 * least / (by_program[program][0] / traced)
