"""The OLMoE cell's benchmark code on the CPU: the reference against the
dense reference where the two must coincide, ``flops_moe.py`` by hand
count, and the by-kernel trace reduction with its reader on a hand-made
trace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_moe, manifest, reference, reference_olmoe
from benchmark.readers import kernel_time
from benchmark.runners import serve_moe
from ray_tpu.models import llama

OLMOE = manifest.load_json(
    manifest.HERE + "/configs/olmoe-1b-7b-0125-l12.json")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_one_expert_top_one_no_qk_norm_is_the_dense_reference():
    """softmax over ONE expert is 1: the routed block is the SwiGLU
    block, so the two references must agree on the same weights."""
    config = llama.LlamaConfig.tiny(
        num_experts=1, num_experts_per_tok=1, intermediate_size=32,
        dtype=jnp.float32)
    params = llama.init_params(config, jax.random.PRNGKey(2))
    layers = dict(params["layers"])
    for moe_key, key in (("moe_gate", "w_gate"), ("moe_up", "w_up"),
                         ("moe_down", "w_down")):
        layers[key] = layers.pop(moe_key)[:, 0]
    del layers["w_router"]
    tokens = np.random.default_rng(0).integers(0, config.vocab_size, 30)
    np.testing.assert_allclose(
        np.asarray(reference_olmoe.logits(params, tokens, config)),
        np.asarray(reference.logits(dict(params, layers=layers), tokens,
                                    config)), rtol=0, atol=1e-5)
    routes = reference_olmoe.router_choices(params, tokens, config)
    assert routes.shape == (config.num_layers, 30, 1) and not routes.any()


def test_program_config_carries_the_published_keys():
    config = serve_moe.olmoe_config(OLMOE, max_seq_len=1024)
    assert (config.num_experts, config.num_experts_per_tok,
            config.intermediate_size, config.hidden_size) == (64, 8, 1024, 2048)
    assert config.qk_norm and not config.norm_topk_prob
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        16, 16, 128)
    # 419.6M a layer, 5.24B held: the arithmetic in the file's `reduced`.
    assert llama.num_params(config) == 12 * 419_569_664 + 2 * 50304 * 2048 + 2048


def test_flops_moe_by_hand():
    # One expert: 3 x 2048 x 1024 weights; 6 x 2048 x 1024 FLOPs an assignment.
    assert flops_moe.expert_params(OLMOE) == 6_291_456
    assert flops_moe.gmm_flops(OLMOE, 384) == 2 * 6_291_456 * 384
    # 64 touched experts' weights in bf16, 384 rows of 2048 in and out.
    assert flops_moe.gmm_bytes(OLMOE, 384, 64) == (
        64 * 6_291_456 * 2 + 2 * 384 * 2048 * 2)
    # 48 slots, all experts touched: HBM-bound, 808.5 MB a layer, 12 layers.
    least = flops_moe.tick_gmm_seconds(OLMOE, 48, 1.0, V5E)
    assert least == pytest.approx(12 * 808_452_096 / 819e9)
    assert 2 * 6_291_456 * 384 / 197e12 < 808_452_096 / 819e9
    # Half the experts touched: half the weights, the same rows.
    half = flops_moe.tick_gmm_seconds(OLMOE, 48, 0.5, V5E)
    assert half == pytest.approx(12 * (32 * 6_291_456 * 2 + 3_145_728) / 819e9)


def _custom_call(name):
    return (f"%{name} = bf16[384,1024]{{1,0}} custom-call(...), "
            'custom_call_target="tpu_custom_call"')


HAND_MADE = {"/device:TPU:0": {
    trace_name: events for trace_name, events in (
        ("XLA Modules", [("jit_tick(123)", 0, 1000),
                         ("jit_prefill(9)", 2000, 500),
                         ("jit_tick(123)", 3000, 1000)]),
        ("XLA Ops", [("%while.1 = while(...)", 0, 1000),
                     (_custom_call("moe_gmm.3"), 100, 200),
                     (_custom_call("moe_gmm.4"), 400, 100),
                     (_custom_call("paged_decode_attn.9"), 600, 50),
                     (_custom_call("moe_gmm.7"), 2100, 300),
                     ("%fusion.5 = bf16[4]{0} fusion(...)", 3000, 1000),
                     (_custom_call("moe_gmm.3"), 3100, 250)]))}}


def test_by_kernel_keeps_own_time_and_calls_by_name_and_program():
    out = serve_moe.by_kernel(HAND_MADE)
    gmm = out["kernels"]["moe_gmm"]
    assert gmm["jit_tick"] == [pytest.approx(550e-9), 3]
    assert gmm["jit_prefill"] == [pytest.approx(300e-9), 1]
    assert out["kernels"]["paged_decode_attn"]["jit_tick"][1] == 1
    assert out["programs"]["jit_tick"] == [pytest.approx(2000e-9), 2]
    assert serve_moe.by_kernel({}) == {"kernels": {}, "programs": {}}


def test_kernel_time_reader():
    trace = dict(serve_moe.by_kernel(HAND_MADE), busy_s=2500e-9)
    ctx = {"trace": trace, "config": OLMOE, "engine": {"num_slots": 48},
           "device": {"kind": "TPU v5 lite"},
           "registry_before": {kernel_time.TOUCHED + "_sum": 1.0,
                               kernel_time.TOUCHED + "_count": 1},
           "registry_after": {kernel_time.TOUCHED + "_sum": 4.0,
                              kernel_time.TOUCHED + "_count": 4}}
    share = kernel_time.read(ctx, kernel="moe_gmm", stat="time_share")
    assert share == pytest.approx(100 * 850 / 2500)
    roof = kernel_time.read(ctx, kernel="moe_gmm",
                            stat="tick_roofline_share", program="jit_tick")
    least = flops_moe.tick_gmm_seconds(OLMOE, 48, 1.0, V5E)
    assert roof == pytest.approx(100 * least / (550e-9 / 2))
    # A program without the kernel (the parent commit), or a trace
    # reduced without the by-kernel part: nothing, and no error.
    assert kernel_time.read({"trace": {"busy_s": 1.0}}, kernel="moe_gmm",
                            stat="time_share") is None
    assert kernel_time.read(dict(ctx, trace=dict(trace, kernels={})),
                            kernel="moe_gmm", stat="tick_roofline_share",
                            program="jit_tick") is None


def test_tokens_in_window_counts_what_arrived_inside_it():
    def rec(first, times, prompt=10, done=True):
        return {"first": first, "t": times, "n": len(times),
                "max_tokens": len(times), "prompt_tokens": prompt,
                "done": done, "error": None, "bad": 0,
                "last": times[-1] if times else None}

    records = [
        rec(1.0, [1.0, 2.0, 3.0]),                  # ended inside: 3 + 10
        rec(38.0, [38.0, 39.5, 40.5, 41.0]),        # ends in the drain: 2 + 10
        rec(40.2, [40.2, 40.4]),                    # prefilled after the close
        dict(rec(5.0, [5.0, 6.0]), done=False),     # cut short: nothing
    ]
    assert serve_moe.tokens_in_window(records, 40.0) == 13 + 12
