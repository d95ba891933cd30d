"""TPU compute ops: attention kernels, sequence parallelism, MoE, norms."""

from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.moe import init_moe_params, moe_layer, router_topk
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.paged_decode_attention import decode_attention_reference
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies

__all__ = [
    "apply_rope", "decode_attention_reference", "flash_attention",
    "init_moe_params", "layer_norm", "mha_reference", "moe_layer",
    "ring_attention", "rms_norm",
    "rope_frequencies", "router_topk", "ulysses_attention",
]
