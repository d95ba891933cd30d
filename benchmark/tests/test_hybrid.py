"""The hybrid cell's benchmark code on the CPU: the program's config
from the published keys (and what is refused), ``flops_ssm.py`` by hand
count, and the roofline reader on a hand-made by-kernel trace."""

import pytest

from benchmark import flops_ssm, manifest
from benchmark.readers import kernel_time, ssm_roofline
from benchmark.runners import serve_hybrid, serve_moe
from ray_tpu.models import llama

GRANITE = manifest.load_json(
    manifest.HERE + "/configs/granite-4.0-h-small-l6.json")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_program_config_carries_the_published_keys():
    config = serve_hybrid.granite_hybrid_config(GRANITE, max_seq_len=1024)
    assert config.layer_types == ("mamba",) * 5 + ("attention",)
    assert (config.state_layers, config.attn_layers) == (5, 1)
    assert (config.num_experts, config.num_experts_per_tok,
            config.intermediate_size, config.shared_intermediate_size,
            config.hidden_size) == (72, 10, 768, 1536, 4096)
    assert (config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state,
            config.mamba_n_groups, config.mamba_d_conv) == (128, 64, 128, 1, 4)
    assert (config.num_heads, config.num_kv_heads, config.head_dim) == (
        32, 8, 128)
    assert not config.rope and config.attn_scale == 1 / 128
    assert (config.embedding_multiplier, config.residual_multiplier,
            config.logits_scaling) == (12.0, 0.22, 16.0)
    assert config.tie_word_embeddings and config.norm_topk_prob
    # 800.9M a mamba layer, 740.6M the attention layer, 411.0M the tied
    # embedding: the arithmetic in the file's `reduced`.
    assert llama.num_params(config) == (
        5 * 800_941_696 + 740_597_760 + 100352 * 4096 + 4096)


@pytest.mark.parametrize("key,value", [
    ("mamba_proj_bias", True), ("attention_bias", True),
    ("mamba_conv_bias", False), ("mamba_expand", 4),
    ("tie_word_embeddings", False), ("hidden_act", "gelu")])
def test_what_the_program_does_not_run_is_refused(key, value):
    with pytest.raises(ValueError, match="does not run"):
        serve_hybrid.granite_hybrid_config(dict(GRANITE, **{key: value}))


def test_flops_ssm_by_hand():
    # One sequence, one layer: 128 x 64 x 128 state elements.
    assert flops_ssm.state_elements(GRANITE) == 1_048_576
    assert flops_ssm.ssm_layers(GRANITE) == 5
    assert flops_ssm.step_flops(GRANITE, 48) == 5 * 1_048_576 * 48
    # float32 state in and out; x and y [8192] and B and C [128] in
    # bf16, dt [128] in float32.
    one = 2 * 1_048_576 * 4 + (2 * 8192 + 2 * 128) * 2 + 128 * 4
    assert flops_ssm.step_bytes(GRANITE, 1) == one == 8_422_400
    # 48 slots, 5 layers: HBM-bound, 404 MB a layer.
    least = flops_ssm.tick_step_seconds(GRANITE, 48, V5E)
    assert least == pytest.approx(5 * 48 * one / 819e9)
    assert 5 * 1_048_576 * 48 / 197e12 < 48 * one / 819e9


def _custom_call(name):
    return (f"%{name} = f32[48,128,128]{{2,1,0}} custom-call(...), "
            'custom_call_target="tpu_custom_call"')


HAND_MADE = {"/device:TPU:0": {
    trace_name: events for trace_name, events in (
        ("XLA Modules", [("jit_tick(7)", 0, 4000),
                         ("jit_prefill(9)", 5000, 500),
                         ("jit_tick(7)", 6000, 4000)]),
        ("XLA Ops", [("%while.1 = while(...)", 0, 4000),
                     (_custom_call("ssm_step.3"), 100, 600),
                     (_custom_call("moe_gmm.4"), 800, 100),
                     (_custom_call("paged_decode_attn.9"), 1000, 50),
                     ("%while.1 = while(...)", 6000, 4000),
                     (_custom_call("ssm_step.3"), 6100, 700)]))}}


def test_ssm_roofline_reader():
    trace = dict(serve_moe.by_kernel(HAND_MADE), busy_s=8500e-9)
    ctx = {"trace": trace, "config": GRANITE, "engine": {"num_slots": 48},
           "device": {"kind": "TPU v5 lite"}}
    share = kernel_time.read(ctx, kernel="ssm_step", stat="time_share")
    assert share == pytest.approx(100 * 1300 / 8500)
    roof = ssm_roofline.read(ctx, kernel="ssm_step", program="jit_tick")
    least = flops_ssm.tick_step_seconds(GRANITE, 48, V5E)
    assert roof == pytest.approx(100 * least / (1300e-9 / 2))
    # A program without the kernel (the parent commit), a trace reduced
    # without the by-kernel part, or a configuration without state
    # layers: nothing, and no error.
    for other in ({"trace": {"busy_s": 1.0}},
                  dict(ctx, trace=dict(trace, kernels={})),
                  dict(ctx, trace=dict(trace, programs={})),
                  dict(ctx, config={"layer_types": ["attention"]}),
                  dict(ctx, config={})):
        assert ssm_roofline.read(other, kernel="ssm_step",
                                 program="jit_tick") is None
