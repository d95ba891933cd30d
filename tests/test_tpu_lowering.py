"""Every Pallas kernel, and the sharded train step, must LOWER for TPU.

Interpret mode (what the rest of tier-1 runs) turns a kernel into plain
HLO and skips the TPU lowering's rules — block shapes in whole trailing
tiles, rank-1 SMEM blocks, Mosaic kernels under GSPMD — so a kernel that
can only be interpreted passes every CPU test and is refused on the chip.
``jax.export`` with ``platforms=["tpu"]`` runs that lowering on a CPU
host in milliseconds; ``RAY_TPU_PALLAS_INTERPRET=0`` makes the
dispatchers emit the real kernel. (Lowering is not compiling: Mosaic's
own checks and VMEM limits need the chipless AOT compile described in
README "Development", or the chip.) The last tests here ARE such
compiles: the decode tick and the speculative tick for a described v5e,
whose HLO must hold no copy of a layer's arena slab. Run them before
asking for chip time on the engine's forward.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.models.training import ShardedTrainer
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.paged_decode_attention import (paged_decode_attention,
                                                paged_kv_write)
from ray_tpu.parallel import MeshConfig, make_mesh

S = jax.ShapeDtypeStruct
BF16 = jnp.bfloat16

def _prefill_batches(eng):
    """Prefill batches ``eng`` ran: ``CB_PREFILL_MS`` books one each."""
    from ray_tpu._private import metrics_defs as mdefs

    return mdefs.CB_PREFILL_MS.totals(eng._mtags)[1]



@pytest.fixture(autouse=True)
def _real_kernels(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "0")


def _mosaic_calls(fn, *specs) -> int:
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs)
    return exported.mlir_module().count("tpu_custom_call")


def _kernel_names(module: str) -> list:
    """The Pallas ``name=`` of every Mosaic call in ``module``, sorted."""
    names = sorted(re.findall(r'kernel_name = "([^"]+)"', module))
    assert len(names) == module.count("tpu_custom_call")
    return names


# (q heads, kv heads): the 953M config's MHA, and one GQA shape.
HEADS = [(16, 16), (32, 8)]


def _flash_sum(q, k, v):
    return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32))


@pytest.mark.parametrize("hq,hkv", HEADS)
def test_flash_forward_and_gradient_lower(hq, hkv):
    q = S((2, 2048, hq, 128), BF16)
    kv = S((2, 2048, hkv, 128), BF16)
    fwd = functools.partial(flash_attention, causal=True)
    assert _mosaic_calls(fwd, q, kv, kv) == 1

    # forward (once: it hands out and lse to the backward as residuals; a
    # bare jax.grad has no checkpoint, so nothing to replay) + dq + dk/dv
    assert _mosaic_calls(
        jax.grad(_flash_sum, argnums=(0, 1, 2)), q, kv, kv) == 3


@pytest.mark.parametrize("hq,hkv", HEADS)
def test_flash_statistics_cross_hbm_as_rows(hq, hkv):
    """The log-sum-exp and the backward's delta pass between the kernels
    (and into a checkpoint's saved residuals) as ``[B, H, 1, S]`` rows. A
    ``[B, H, S, 1]`` float32 column is padded to 128 lanes a value on the
    chip: reshaping the saved lse through it cost 7.3 ms of a 909 ms
    ``train_fsdp4`` step (PR 35)."""
    q = S((2, 2048, hq, 128), BF16)
    kv = S((2, 2048, hkv, 128), BF16)
    module = jax.export.export(
        jax.jit(jax.grad(_flash_sum, argnums=(0, 1, 2))),
        platforms=["tpu"])(q, kv, kv).mlir_module()
    assert f"tensor<2x{hq}x1x2048xf32>" in module
    assert "x2048x1xf32>" not in module


def test_flash_kernels_feed_the_mxu_bf16_and_mask_only_the_diagonal(
        monkeypatch):
    """The Mosaic text of the three kernels at ``train_fsdp4``'s shape.
    Every product takes bf16 operands when the inputs are bf16 (float32
    out): an upcast operand is a pass of the vector unit, which has no
    bf16 lanes on a v5e. And a kernel is four predicated bodies, init |
    interior | diagonal | finalize: the interior one, every score live,
    builds no mask (no iota), the diagonal one does, and the backward
    kernels cut a diagonal block 4 x 4, ten products of sixteen (PR 56)."""
    from jax._src import tpu_custom_call

    modules = []
    real = tpu_custom_call._lower_mosaic_module_to_asm

    def spy(module, **kw):
        modules.append(str(module))
        return real(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", spy)
    q = S((1, 4096, 32, 128), BF16)
    kv = S((1, 4096, 8, 128), BF16)
    jax.export.export(jax.jit(jax.grad(_flash_sum, argnums=(0, 1, 2))),
                      platforms=["tpu"])(q, kv, kv)
    assert len(modules) == 3                        # fwd, dq, dkv
    # (products a live piece, pieces of a diagonal block)
    for module, (products, pieces) in zip(modules, [(2, 1), (3, 7), (4, 7)]):
        matmuls = [l for l in module.splitlines() if "tpu.matmul" in l]
        for line in matmuls:
            operands = re.findall(r"vector<[0-9x]+x(\w+)>", line.split(" : ")[1])
            assert operands == ["bf16", "bf16", "f32", "f32"], line
        init, interior, diagonal, finalize = module.split("scf.if")[1:]
        assert [b.count("tpu.matmul") for b in (
            init, interior, diagonal, finalize)] == [
                0, products, products * pieces, 0]
        assert "iota" not in interior
        assert "iota" in diagonal


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("layers", [None, 16])
@pytest.mark.parametrize("limited", [False, True])
def test_paged_decode_lowers(hq, hkv, kv_dtype, layers, limited):
    """32 slots, block 64, 8-block tables over a 257-block arena: one
    slab, and the whole 16-layer arena read at a traced layer; every
    slot live, and freed slots told apart by ``limits``. The grid's
    bound is the traced count of live blocks either way."""
    q = S((32, hq, 128), BF16)
    tables, positions = S((32, 8), jnp.int32), S((32,), jnp.int32)
    lead = () if layers is None else (layers,)
    arena = S(lead + (257, hkv, 64, 128),
              jnp.int8 if kv_dtype == "int8" else BF16)
    scales = [S(lead + (257, hkv, 64), jnp.float32)] * 2 \
        if kv_dtype == "int8" else [None, None]

    def fn(q, k, v, t, p, ks, vs, li, lim):
        return paged_decode_attention(q, k, v, t, p, k_scale=ks, v_scale=vs,
                                      layer=li, limits=lim, use_kernel=True)

    li = None if layers is None else S((), jnp.int32)
    lim = S((32,), jnp.int32) if limited else None
    assert _mosaic_calls(fn, q, arena, arena, tables, positions, *scales,
                         li, lim) == 1


# The MHA 16/16 and GQA 32/8 shapes, the latent row (``mla.py``) and the
# two wide heads of the linear-attention cell's full layers.
@pytest.mark.parametrize("hkv,d", [(16, 128), (8, 128), (1, 640), (2, 256)])
@pytest.mark.parametrize("kind", ["bf16", "int8", "scale"])
@pytest.mark.parametrize("width", [1, 5])
def test_paged_kv_write_lowers(hkv, d, kind, width):
    """The in-place write: a tick's one token a slot and a verify
    window's five, into K/V of either dtype (a tile of a 64-row block a
    grid step) and the fp32 scale rows (the block)."""
    trailing = () if kind == "scale" else (d,)
    dtype = {"bf16": BF16, "int8": jnp.int8, "scale": jnp.float32}[kind]
    arena = S((16, 257, hkv, 64) + trailing, dtype)
    new = S((32, width, hkv) + trailing, dtype)
    where = S((32, width), jnp.int32)
    assert _mosaic_calls(paged_kv_write, arena, new, S((), jnp.int32),
                         where, where) == 1


@pytest.mark.parametrize("remat_policy", ["full", "attn_out", "mlp_only"])
@pytest.mark.parametrize("fsdp", [1, 4])
def test_sharded_train_step_lowers_with_flash(fsdp, remat_policy):
    """GSPMD cannot partition a Mosaic kernel: on a mesh of more than one
    device the flash call must sit inside a shard_map, or this raises
    ``Mosaic kernels cannot be automatically partitioned``.

    And the checkpointed layer's backward holds NO second flash forward:
    every remat policy keeps the kernel's output and log-sum-exp by name
    (through the shard_map too), so the step is one forward kernel and the
    two backward kernels inside the layer scans (PR 35; the replay was
    2.4-2.7% of a step on the chip)."""
    config = llama.LlamaConfig.tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
        max_seq_len=128, remat=True, remat_policy=remat_policy)
    mesh = make_mesh(MeshConfig(fsdp=fsdp), devices=jax.devices()[:fsdp])
    trainer = ShardedTrainer(config, mesh)
    state = jax.eval_shape(trainer._init._jitted, jax.random.PRNGKey(0))
    batch = {"tokens": S((4, 128), jnp.int32), "mask": S((4, 128), jnp.int32)}
    with mesh:
        exported = jax.export.export(
            trainer._step._jitted, platforms=["tpu"])(state, batch)
    assert _kernel_names(exported.mlir_module()) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


# ------------------------------------------- the compiled tick's arena moves

# Mistral-7B widths (benchmark/configs/mistral-7b-v0.3-l16.json) and
# serve_chat's engine sizes; two layers are enough for a layer loop.
_TICK_LAYERS, _TICK_SLOTS, _TICK_BLOCKS, _TICK_BS, _TICK_LEN = (
    2, 48, 1000, 64, 2048)
# The cell's own depth, for what depends on how the compiler places the
# WEIGHTS: at two layers a weight's whole stack fits the chip's fast
# memory and is prefetched there (slice-start/slice-done), which no
# served model's program does; at 16 the compile names the instructions
# the chip's trace names (PR 30).
_CELL_LAYERS = 16


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of a v5e host that is described, not attached.
    Built inside a fixture: only the worker that runs this file may load
    libtpu."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_chip(v5e_host):
    """One chip of that host."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_host[0])


@functools.lru_cache(maxsize=None)
def compile_paged_tick(sharding, kv_dtype, spec_k=0, layers=_TICK_LAYERS):
    """AOT-compile the paged decode tick at the sizes above for
    ``sharding``'s chip; with ``spec_k``, the speculative tick (that
    many one-layer self-drafts, then a verify window of ``spec_k + 1``).
    The parameters are the ENGINE's tree (``cb.init_engine_params``)."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import PagedKVCache

    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=layers, num_heads=32, num_kv_heads=8,
        head_dim=128, max_seq_len=_TICK_LEN, rope_theta=1e6,
        rms_eps=1e-5)

    def spec(a):
        return S(a.shape, a.dtype, sharding=sharding)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, _TICK_BLOCKS, _TICK_BS,
        kv_dtype=kv_dtype)))
    row = S((_TICK_SLOTS,), jnp.int32, sharding=sharding)
    tables = S((_TICK_SLOTS, _TICK_LEN // _TICK_BS), jnp.int32,
               sharding=sharding)
    step = S((), jnp.int32, sharding=sharding)
    if spec_k:
        tick = functools.partial(
            cb._spec_tick_paged, config=cfg, k=spec_k, n_draft=1,
            use_kernel=True, sampling=cb.SamplingParams())
    else:
        tick = functools.partial(cb._decode_tick_paged, config=cfg,
                                 use_kernel=True)
    # Argument order and donation as the engine's ``cb_tick`` and
    # ``cb_spec_tick``.
    return jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, cache, step).compile()


# Instructions that move no bytes of their own.
_FREE = ("custom_call_target=\"tpu_custom_call\"", " parameter(",
         " get-tuple-element(", " tuple(", " while(", " bitcast(")


def arena_moves(hlo_text: str, trailing: str):
    """Instructions of compiled HLO whose result is one layer's slab or
    the whole arena (``[L?, NB, KVH, bs`` + ``trailing`` + ``]``) and
    that are not one of the two kernels: the slices, relayout copies,
    scatters and write-backs the in-place write exists to remove. A
    parameter, a loop's tuple plumbing and a bitcast move no bytes."""
    shaped = re.compile(rf"= \(?\w+\[(\d+,)?{_TICK_BLOCKS},8,{_TICK_BS}"
                        rf"{trailing}\]")
    return [line.strip() for line in hlo_text.splitlines()
            if shaped.search(line) and not any(f in line for f in _FREE)]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_compiled_paged_tick_moves_no_arena_slab(v5e_chip, kv_dtype):
    """48 slots over a 1000-block arena: each layer of the compiled tick
    touches the arena through ``paged_kv_write`` (K, V, and the two
    scale sidecars of an int8 arena) and ``paged_decode_attn`` and
    nothing else, and the program needs less scratch than one slab."""
    compiled = compile_paged_tick(v5e_chip, kv_dtype)
    hlo = compiled.as_text()
    assert arena_moves(hlo, ",128") == []
    # The fp32 scale sidecar [L, NB, KVH, bs] rests in HBM with NB as
    # its minor axis (the TPU's own choice for a 64-wide last axis), so
    # the entry computation relayouts it once in and once out for
    # Mosaic: 4 bytes a token row, outside the layer loop. No layer may.
    layer_loop = hlo[:hlo.index("\nENTRY ")]
    assert arena_moves(layer_loop, "") == []
    writes = 4 if kv_dtype == "int8" else 2
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == writes
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 1
    # Scratch: the kernel's schedule, made once before the layer loop
    # (48 x 8 visits of four bf16 blocks against 48 slot ends, in fusions
    # and no gather: a gather's index vectors pad to 128 lanes, 0.4 MB
    # each), and the zeroed output it fills in, 0.7-0.8 MB together
    # since PR 26 (782,848 and 879,616 bytes at PR 33); an int8 arena
    # adds the relayout of its scale sidecars. Not one block-row of a slab more
    # (0 and 1,408,512 bytes at PR 25, whose kernel had no schedule).
    scratch = compiled.memory_analysis().temp_size_in_bytes
    assert scratch <= (1 << 20) + (1_408_512 if kv_dtype == "int8" else 0)
    # The schedule reaches the layer body as loop state: XLA does not
    # hoist it, so nothing there may compute a visit list.
    body = next(c for c in hlo.split("\n\n")
                if re.search(r"%paged_decode_attn[.\d]* = ", c))
    # (Slot and first block a visit; a table entry a visit and sub-block,
    # which is also the flattened tables' length.)
    # 8 kv heads x 64 x 128: a megabyte is four bf16 blocks, eight int8.
    per = 8 if kv_dtype == "int8" else 4
    visits = _TICK_SLOTS * -(-(_TICK_LEN // _TICK_BS) // per) + 1
    listed = [line.strip() for line in body.splitlines()
              if re.search(rf"= s32\[({visits}|{visits * per})\]", line)]
    assert len(listed) >= 3
    assert [line for line in listed
            if " get-tuple-element(" not in line] == []


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_compiled_tick_keeps_its_operands_and_donates_the_arena_alone(
        v5e_chip, kv_dtype):
    """What the engine's pipeline leans on (PR 28 changed no program):
    the tick takes the parameters, tokens, positions, tables, limits,
    arena and step it took, and aliases onto its outputs the ARENA's
    buffers only. The token vector it returns is a buffer of its own, so
    tick n's row can still be fetched after tick n+1, which reads it,
    has been dispatched."""
    hlo = compile_paged_tick(v5e_chip, kv_dtype).as_text()
    entry = hlo[hlo.index("\nENTRY "):]
    operands = {int(n) for n in re.findall(r" parameter\((\d+)\)", entry)}
    arenas = 4 if kv_dtype == "int8" else 2
    # embed, final_norm, lm_head and nine stacked layer weights; tokens,
    # positions, tables, limits; the arena; the step counter.
    assert operands == set(range(12 + 4 + arenas + 1))
    header = hlo[:hlo.index("\n")]
    aliased = re.findall(r"\{[\d, ]*\}: \((\d+), ", header)
    assert sorted(int(n) for n in aliased) == list(range(16, 16 + arenas))


def projection_weight_moves(hlo_text: str, heads: int, kv_heads: int,
                            embed: int, head_dim: int = 128):
    """Instructions of compiled HLO whose result is ONE LAYER's ``wq``,
    ``wk`` or ``wv`` in either axis order and that are not reads inside
    a fusion: the per-layer slice copies (``constant_dynamic-slice_
    fusion bf16[1,4096,32,128]``: 9% of serve_chat's tick before PR 30),
    relayout copies and asynchronous slices. A ``slice`` or
    ``dynamic-slice`` INSIDE a fused computation is the fusion's own read
    of its operand (the matmul taking the stacked weight at the layer
    index) and writes nothing back."""
    dims = "|".join(f"{a},{b}" for h in {heads, kv_heads}
                    for a, b in ((h, embed), (embed, h)))
    shaped = re.compile(rf"= \(?\w+\[1,({dims}),{head_dim}\]")
    moves = []
    for computation in hlo_text.split("\n\n"):
        fused = "fused_computation" in computation.lstrip().split("(")[0]
        for line in computation.splitlines():
            if not shaped.search(line) or any(f in line for f in _FREE):
                continue
            if fused and (" dynamic-slice(" in line or " slice(" in line):
                continue
            moves.append(line.strip())
    return moves


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("spec_k", [0, 4])
def test_compiled_tick_copies_no_projection_weights(v5e_chip, kv_dtype,
                                                    spec_k):
    """The engine holds ``wq``/``wk``/``wv`` heads-major (``[L, H, E,
    D]``, :func:`llama.heads_major`), so the tick's projections read the
    STACKED weights in place at the layer index, as ``wo`` and the MLP
    do. In the canonical ``[L, E, H, D]`` every layer first copied its
    three slices out (50 MB a layer written and read back: 1.1 ms of an
    11.5 ms tick on the chip), and the speculative tick held 18 such
    slices and copies."""
    hlo = compile_paged_tick(v5e_chip, kv_dtype, spec_k,
                             _CELL_LAYERS).as_text()
    assert projection_weight_moves(hlo, 32, 8, 4096) == []
    # The guard sees what it guards against: the parent's instruction.
    assert projection_weight_moves(
        "%body (p: bf16[16,4096,32,128]) -> bf16[1,4096,32,128] {\n"
        "  %constant_dynamic-slice_fusion.6 = bf16[1,4096,32,128]"
        "{3,2,1,0:T(8,128)(2,1)S(1)} fusion(%p, %i), kind=kLoop\n}",
        32, 8, 4096) != []


def test_admissions_after_warm_up_compile_nothing():
    """The engine's own programs on the CPU, counted by ``xla_monitor``
    as ``compiles_in_window`` counts them: once a warm-up has admitted
    into a running batch (a re-upload with a tick in flight, which is
    where ``cb_merge_tokens`` runs), a run with admissions, ends and a
    cancel compiles nothing, and ``cb_tick`` keeps its one signature."""
    from ray_tpu._private import xla_monitor
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    def compiles():
        return {s["name"]: s["compiles"]
                for s in xla_monitor.all_program_stats()}

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    cold = compiles()
    eng = ContinuousBatcher(cfg, num_slots=4, max_len=64, block_size=16)
    eng.submit([1, 2, 3], max_new_tokens=6)
    eng.step()
    eng.submit([4, 5, 6, 7], max_new_tokens=3)     # joins a running batch
    eng.run_to_completion()
    warm = compiles()
    gained = {n: warm[n] - cold.get(n, 0) for n in warm}
    assert gained["cb_tick"] == 1 and gained["cb_merge_tokens"] == 1
    assert gained["cb_prefill"] == 1
    rids = []
    for step in range(40):
        if step < 4:        # one admission a step: the one-row prefill
            rids.append(eng.submit([step + 1, 2, 3, 4, 5],
                                   max_new_tokens=4 + 3 * step))
        if step == 6:
            assert eng.cancel(rids[3])
            rids.append(eng.submit([6, 6, 6, 6], max_new_tokens=5))
        eng.step()
    assert not eng.has_work() and _prefill_batches(eng) == 2 + 5
    assert compiles() == warm
    assert eng._tick._cache_size() == 1


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_compiled_spec_tick_moves_no_arena_slab(v5e_chip, kv_dtype):
    """The speculative tick runs the decode tick's own forward at other
    widths (four one-layer drafts of a window of 1, then a verify window
    of 5), so the tick's guard holds for it too: the arena is touched by
    the two kernels alone, the verify layer writing once per arena and
    attending once per window position."""
    hlo = compile_paged_tick(v5e_chip, kv_dtype, spec_k=4).as_text()
    assert arena_moves(hlo, ",128") == []
    assert arena_moves(hlo[:hlo.index("\nENTRY ")], "") == []
    arenas = 4 if kv_dtype == "int8" else 2
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == 5 * arenas
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 4 + 5


# --------------------------------------------- the routed block (OLMoE)

def _moe_gmm_module(rows, x, k, n, weights):
    """The exported module of one grouped call: ``weights`` stacked
    ``[12, x, k, n]`` arrays read at a traced layer index."""
    from ray_tpu.ops import moe

    fn = functools.partial(
        moe.grouped_swiglu if weights == 2 else moe.grouped_matmul,
        use_kernel=True)
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(
        S((rows, k), BF16), *[S((12, x, k, n), BF16)] * weights,
        S((x,), jnp.int32), S((), jnp.int32)).mlir_module()


@pytest.mark.parametrize("rows", [384, 8192])
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_moe_gmm_lowers(rows, k, n):
    """The grouped kernel at OLMoE's widths, decode and prefill row
    counts, reading 12 stacked layers at a traced layer index."""
    module = _moe_gmm_module(rows, 64, k, n, 1)
    assert _kernel_names(module) == ["moe_gmm"]


# (rows, held experts, K, N, the window's width): OLMoE's tick and
# prefill (a 4 MiB matrix, fetched whole), Granite's tick (K 4096),
# Trinity's and Kimi's held shares (K 7168): windows of 2 MiB, or the
# narrowest of whole 128-lane tiles that halving reaches.
FUSED_GMM = [(384, 64, 2048, 1024, 1024), (8192, 64, 2048, 1024, 1024),
             (480, 72, 4096, 768, 384), (192, 32, 3072, 3072, 384),
             (768, 12, 7168, 2048, 128), (8192, 12, 7168, 2048, 128)]


@pytest.mark.parametrize("rows,x,k,n,width", FUSED_GMM)
def test_moe_gmm_fused_gate_and_up_lowers(rows, x, k, n, width):
    """Gate and up in ONE Mosaic call named ``moe_gmm`` (the name the
    benchmark's readers match), declaring the module's VMEM budget; what
    the tile rule gives it to hold, double-buffered (two weight windows,
    the row tile, the output tile) plus the two float32 accumulators,
    fits that budget with a quarter to spare."""
    from ray_tpu.ops import moe

    module = _moe_gmm_module(rows, x, k, n, 2)
    assert _kernel_names(module) == ["moe_gmm"]
    assert re.findall(r'scoped_memory_configs[^\]]*size(?:\\22|")?: (\d+)',
                      module) == [str(moe.GMM_VMEM_BYTES)]
    tm, tn = moe._gmm_tiles(rows, k, n, x, 2)
    assert tn == width and tn % 128 == 0 and tm % 16 == 0
    assert k * tn * 2 <= moe.GMM_WHOLE_BYTES
    held = 2 * (2 * k * tn + tm * k + tm * tn) * 2 + 2 * tm * tn * 4
    assert held <= moe.GMM_VMEM_BYTES * 3 // 4


@functools.lru_cache(maxsize=None)
def compile_olmoe_tick(sharding, layers):
    """serve_moe_decode's tick for ``sharding``'s chip: published widths,
    48 slots, 512 blocks, the ENGINE's parameter tree."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import PagedKVCache

    cfg = llama.LlamaConfig.olmoe_1b_7b(num_layers=layers, max_seq_len=1024)

    def spec(a):
        return S(a.shape, a.dtype, sharding=sharding)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, 512, 64, kv_dtype="bf16")))
    row = S((48,), jnp.int32, sharding=sharding)
    tables = S((48, 16), jnp.int32, sharding=sharding)
    step = S((), jnp.int32, sharding=sharding)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    return jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, cache, step).compile()


def test_compiled_olmoe_tick_copies_no_expert_weights(v5e_chip):
    """serve_moe_decode's tick (two layers): each layer calls ``moe_gmm``
    twice (gate and up in one call, then down; three before PR 37) on
    the STACKED expert weights, and no other instruction
    has a layer's expert-weight shape: a per-layer slice would copy
    805 MB a layer a tick. MHA: both paged kernels compile at 16 KV
    heads, group 1."""
    compiled = compile_olmoe_tick(v5e_chip, 2)
    hlo = compiled.as_text()
    shaped = re.compile(r"= \(?\w+\[(\d+,)?64,(2048,1024|1024,2048)\]")
    assert [line.strip() for line in hlo.splitlines()
            if shaped.search(line) and not any(f in line for f in _FREE)] == []
    assert len(re.findall(r"%moe_gmm[.\d]* = ", hlo)) == 2
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 1
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == 2
    # One expert matrix is 4 MB: the program's scratch is far below it.
    assert compiled.memory_analysis().temp_size_in_bytes < 2048 * 1024 * 2


def test_compiled_olmoe_tick_copies_no_more_projection_weights(v5e_chip):
    """Under QK-norm (a whole-vector norm takes the projection flat
    across heads) the heads-major layout does not free the tick of its
    projection-weight copies as it does Mistral's: at the cell's 12
    layers it holds two slices and two transposing copies where the
    canonical layout held three slices and a relayout. Four such
    instructions by this file's count on either side (+1% of the tick's
    estimated bytes; -0.3% and -0.8% ``tokens_per_s`` on the chip, PR
    30), and never more."""
    hlo = compile_olmoe_tick(v5e_chip, 12).as_text()
    assert len(projection_weight_moves(hlo, 16, 16, 2048)) <= 4


# ------------------------------------------- the state-space family (PR 29)

@pytest.mark.parametrize("heads,p,n", [(128, 64, 128), (32, 64, 128)])
def test_ssm_step_lowers(heads, p, n):
    """The tick's in-place state update at Granite 4.0-H's widths."""
    from ray_tpu.ops import ssm

    f32 = jnp.float32
    fn = functools.partial(ssm.ssm_step, use_kernel=True)
    assert ssm.ssm_applicable(heads, p, n, 1)
    assert _mosaic_calls(
        fn, S((5, 48) + ssm.packed_shape(heads, p, n), f32),
        S((), jnp.int32), S((48, heads, p), f32), S((48, heads), f32),
        S((heads,), f32), S((48, 1, n), BF16), S((48, 1, n), BF16)) == 1


def test_compiled_hybrid_tick_holds_no_copy_of_the_state_cache(v5e_chip):
    """serve_hybrid_decode's tick (published widths, the cell's 5 Mamba-2
    layers and 1 attention layer, 48 slots, 512 blocks): each run's layer
    loop calls ``ssm_step`` once on the WHOLE state cache
    ``[5, 48, 128, 64, 128]`` float32 (1.0 GB), aliased in to out, and no
    other instruction has the cache's or one layer's slab's shape: an XLA
    update in the loop would slice 201 MB out and put it back a layer a
    tick. The conv tails ``[5, 48, 3, 8448]`` move only as one layer's
    slab (2.4 MB). Experts are read in place by both runs' ``moe_gmm``
    (2 calls a run: gate and up in one, then down), and the program's
    scratch is a few MB."""
    import dataclasses

    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import PagedKVCache, StateCache

    full = llama.LlamaConfig.granite_4_0_h_small()
    cfg = dataclasses.replace(full, num_layers=6,
                              layer_types=full.layer_types[:6],
                              max_seq_len=1024)

    def spec(a):
        return S(a.shape, a.dtype, sharding=v5e_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, 512, 64, kv_dtype="bf16")))
    state = jax.tree.map(spec, jax.eval_shape(functools.partial(
        StateCache.create, cfg, 48)))
    assert cache.k.shape[0] == 1 and state.ssm.shape == (5, 48, 128, 64, 128)
    row = S((48,), jnp.int32, sharding=v5e_chip)
    tables = S((48, 16), jnp.int32, sharding=v5e_chip)
    step = S((), jnp.int32, sharding=v5e_chip)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    compiled = jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, (cache, state), step).compile()
    hlo = compiled.as_text()

    def moves(shape):
        shaped = re.compile(rf"= \(?\w+\[(\d+,)?{shape}\]")
        return [line.strip() for line in hlo.splitlines()
                if shaped.search(line) and not any(f in line for f in _FREE)]

    assert moves("48,128,64,128") == []
    # ... written back in place: a dynamic-update-slice on the loop's
    # carry (alone or as a loop fusion's root), never a copy.
    assert all(" dynamic-update-slice(" in line or " fusion(" in line
               for line in moves("5,48,3,8448"))
    assert len(re.findall(r"%ssm_step[.\d]* = ", hlo)) == 1
    assert len(re.findall(r"%moe_gmm[.\d]* = ", hlo)) == 4
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 1
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == 2
    header = hlo[:hlo.index("\n")]
    aliased = re.findall(r"\{[\d, ]*\}: \((\d+), ", header)
    assert len(aliased) == 4        # K, V, the state, the conv tails
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 5 * 48 * 128 * 64 * 128 * 4
    assert memory.temp_size_in_bytes < 8 << 20     # the tails are 12 MB


def test_compiled_window_tick_moves_no_ring_slab_and_no_expert_weight(
        v5e_chip):
    """serve_window_decode's tick (Trinity's published widths; the cell's
    1 dense + 4 routed layers holding 32 of 256 experts, 3 sliding : 1
    full attention, 48 slots, max_len 7168): the four sliding-window
    layers read and write their RING ``[4, 1 + 48 x 66, 8, 64, 128]``
    (3.32 GB) and the full-attention layer the arena ``[1, 1 + 48 x 112,
    ...]`` through the two paged kernels alone, both aliased in to out:
    no other instruction has a ring's, the arena's or one layer's slab's
    shape (PR 24's guard, for the new storage). The held experts ``[4,
    32, 3072, 3072]`` are read in place by ``moe_gmm`` (PR 25's guard):
    three calls a routed run, three runs. Four runs of attention layers
    (the MLP changes after layer 0, the kind at layers 3 and 4): one
    ``paged_decode_attn`` and two ``paged_kv_write`` call sites each."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import PagedKVCache, RingKVCache

    slots, table = 48, 7168 // 64
    cfg = llama.LlamaConfig.trinity_large_preview(
        num_layers=5, num_dense_layers=1, experts_held=(0, 32),
        layer_types=("sliding_attention",) * 3 + ("full_attention",
                                                  "sliding_attention"),
        vocab_size=25024, max_seq_len=7168)

    def spec(a):
        return S(a.shape, a.dtype, sharding=v5e_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, slots * table + 1, 64)))
    ring = jax.tree.map(spec, jax.eval_shape(functools.partial(
        RingKVCache.create, cfg, slots, 64)))
    assert ring.k.shape == (4, 1 + slots * 66, 8, 64, 128)
    assert cache.k.shape == (1, 1 + slots * table, 8, 64, 128)
    assert params["layers"]["moe_gate"].shape == (4, 32, 3072, 3072)
    row = S((slots,), jnp.int32, sharding=v5e_chip)
    tables = S((slots, table), jnp.int32, sharding=v5e_chip)
    step = S((), jnp.int32, sharding=v5e_chip)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    compiled = jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, (cache, ring), step).compile()
    hlo = compiled.as_text()

    def moves(shape):
        shaped = re.compile(rf"= \(?\w+\[(\d+,)?{shape}\]")
        return [line.strip() for line in hlo.splitlines()
                if shaped.search(line) and not any(f in line for f in _FREE)]

    assert moves(f"{1 + slots * 66},8,64,128") == []        # the ring
    assert moves(f"{1 + slots * table},8,64,128") == []     # the arena
    assert moves("32,3072,3072") == []                      # held experts
    assert len(re.findall(r"%moe_gmm[.\d]* = ", hlo)) == 6
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 4
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == 8
    # Both schedules (the rings' and the table's) are made once, in the
    # entry computation (which also holds the runs of one layer): the
    # layer loop's body takes them as loop state, and at most moves a
    # list between memory spaces or flattens the rings' iota table.
    lists = "|".join(str((slots * -(-n // 4) + 1) * per)
                     for n in (66, table) for per in (1, 4))
    loops = [c for c in hlo[:hlo.index("\nENTRY ")].split("\n\n")
             if re.search(r"%paged_decode_attn[.\d]* = ", c)]
    assert len(loops) == 1          # the three sliding routed layers
    listed = [line.strip() for line in loops[0].splitlines()
              if re.search(rf"= s32\[({lists})\]", line)]
    assert len(listed) >= 3
    assert [line for line in listed if not any(
        op in line for op in (" get-tuple-element(", " copy-start(",
                              " copy-done(", " reshape("))] == []
    header = hlo[:hlo.index("\n")]
    aliased = re.findall(r"\{[\d, ]*\}: \((\d+), ", header)
    assert len(aliased) == 4        # arena K, V; ring K, V
    memory = compiled.memory_analysis()
    cache_bytes = 2 * (ring.k.size + cache.k.size) * 2
    assert memory.alias_size_in_bytes >= cache_bytes
    assert abs(cache_bytes / 1e9 - 4.73) < 0.01
    # Weights 8.64 GB + caches 4.73 GB resident; scratch a few MB.
    assert abs(memory.argument_size_in_bytes / 1e9 - 13.38) < 0.02
    assert memory.temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("window", [0, 4096])
def test_paged_decode_attn_lowers_with_a_window(window):
    """The decode kernel at the window cell's head shape (48 query / 8
    KV heads of 128) over a ring of 66 blocks: the lower bound in the
    mask and the modulo in the index map lower for the TPU."""
    from ray_tpu.ops.paged_decode_attention import paged_decode_attention

    q = S((48, 48, 128), jnp.bfloat16)
    arena = S((4, 1 + 48 * 66, 8, 64, 128), jnp.bfloat16)
    tables = S((48, 66), jnp.int32)
    pos = S((48,), jnp.int32)

    def attend(q, k, v, tables, pos, layer):
        return paged_decode_attention(q, k, v, tables, pos, layer=layer,
                                      limits=pos, use_kernel=True,
                                      interpret=False, window=window)

    exported = jax.export.export(jax.jit(attend), platforms=["tpu"])(
        q, arena, arena, tables, pos, S((), jnp.int32))
    assert "tpu_custom_call" in exported.mlir_module()


# The served arenas' block shapes (kv heads, query heads, head size) ->
# blocks a grid step: Mosaic compiles the kernel's own copies at every
# width, not only at the 8-head tick's four.
@pytest.mark.parametrize("hkv,hq,d,kv_dtype,per", [
    (2, 8, 128, "bf16", 16), (2, 16, 256, "bf16", 8),
    (16, 16, 128, "bf16", 2), (32, 32, 128, "bf16", 1),
    (2, 8, 128, "int8", 16), (8, 32, 128, "int8", 8),
])
def test_compiled_paged_decode_at_every_visit_width(v5e_chip, hkv, hq, d,
                                                    kv_dtype, per):
    """A real compile for the described chip (the export above stops
    before Mosaic): sixteen sub-blocks a step are 32 copies into 2 MB of
    double buffer, an int8 arena adds 32 BlockSpec operands of scales."""
    from ray_tpu.ops.paged_decode_attention import visit_blocks

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    quantized = kv_dtype == "int8"
    arena = on_chip((2, 65, hkv, 64, d), jnp.int8 if quantized else BF16)
    assert visit_blocks(arena) == per
    scales = [on_chip((2, 65, hkv, 64), jnp.float32)] * 2 if quantized \
        else [None, None]

    def attend(q, k, v, tables, pos, ks, vs):
        return paged_decode_attention(
            q, k, v, tables, pos, layer=jnp.int32(1), limits=pos,
            k_scale=ks, v_scale=vs, use_kernel=True, interpret=False)

    compiled = jax.jit(attend).lower(
        on_chip((8, hq, d), BF16), arena, arena,
        on_chip((8, 40), jnp.int32), on_chip((8,), jnp.int32),
        *scales).compile()
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ",
                          compiled.as_text())) == 1


# ------------------------------------------------- latent attention (MLA)

_MLA_SLOTS, _MLA_LEN, _MLA_BS = 64, 10240, 64
_MLA_VISIT = 16        # blocks a grid step of the latent kernel


def test_latent_decode_kernel_lowers():
    """Kimi K2's head shape: 64 absorbed queries of 640 lanes (512 latent
    + 64 rope + 64 pad) a slot, values the first 512, blocks of 64, the
    shipped blocks a grid step: one call, which takes the cache ONCE
    (the kernel copies a visit's rows out of it itself; a BlockSpec'd
    view a sub-block was one operand each)."""
    from ray_tpu.ops.latent_decode_attention import (latent_decode_attention,
                                                     latent_visit_blocks)

    nb = _MLA_LEN // _MLA_BS
    q = S((8, 64, 640), BF16)
    arena = S((5, 1 + 8 * nb, 1, _MLA_BS, 640), BF16)
    assert latent_visit_blocks(arena) == _MLA_VISIT
    fn = functools.partial(latent_decode_attention, scale=0.14, rank=512,
                           use_kernel=True)
    exported = jax.export.export(jax.jit(
        lambda q, a, t, p, li: fn(q, a, t, p, layer=li)), platforms=["tpu"])(
        q, arena, S((8, nb), jnp.int32), S((8,), jnp.int32),
        S((), jnp.int32))
    module = exported.mlir_module()
    assert _kernel_names(module) == ["latent_decode_attn"]
    call, = [line for line in module.splitlines()
             if "tpu_custom_call" in line]
    operands = call[call.rindex(" : ("):call.rindex(") -> ")]
    assert operands.count(f"tensor<5x{1 + 8 * nb}x1x{_MLA_BS}x640xbf16>") == 1


def test_latent_prefill_kernel_lowers():
    """A chunk of 1024 queries of 64 heads against a run of 1024 keys
    256 lanes wide (192 padded) and values 128 wide, causal and not."""
    from ray_tpu.ops.latent_prefill_attention import attend_run

    q = S((2, 64, 1024, 256), BF16)
    v = S((2, 64, 1024, 128), BF16)
    for causal in (True, False):
        exported = jax.export.export(jax.jit(functools.partial(
            attend_run, scale=0.14, causal=causal)), platforms=["tpu"])(
            q, q, v)
        assert _kernel_names(exported.mlir_module()) == [
            "latent_prefill_attn"]


def test_compiled_latent_tick_reads_the_cache_through_its_kernels(v5e_chip):
    """The cell ``serve_mla_decode``'s tick, compiled for a described
    v5e at the cell's own sizes (Kimi K2's widths, 5 layers, 12 of 384
    experts, 64 slots x 10240 over 10,241 blocks): each of the two runs'
    layer bodies touches the latent cache through ``paged_kv_write`` and
    ``latent_decode_attn`` and nothing else (no slab of ``[10241, 1, 64,
    640]`` is sliced, copied or scattered; the kernel takes the whole
    cache as ONE operand and no visit's joined ``[1024, 640]`` tile
    exists outside it), the routed run calls
    ``moe_gmm`` twice (gate and up in one call, then down), and beside
    the 11.2 GB of arguments (7.0 GB of weights, 4.2 GB of cache,
    donated) the program needs under 64 MB."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import LatentKVCache

    sharding = v5e_chip
    cfg = llama.LlamaConfig.kimi_k2_7_code(
        vocab_size=20480, num_layers=5,
        layer_types=("latent_attention",) * 5, experts_held=(0, 12),
        max_seq_len=_MLA_LEN)

    def spec(a):
        return S(a.shape, a.dtype, sharding=sharding)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    blocks = 1 + _MLA_SLOTS * (_MLA_LEN // _MLA_BS)
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        LatentKVCache.create, cfg, blocks, _MLA_BS)))
    row = S((_MLA_SLOTS,), jnp.int32, sharding=sharding)
    tables = S((_MLA_SLOTS, _MLA_LEN // _MLA_BS), jnp.int32,
               sharding=sharding)
    step = S((), jnp.int32, sharding=sharding)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    compiled = jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, cache, step).compile()
    hlo = compiled.as_text()
    for name, calls in (("latent_decode_attn", 2), ("paged_kv_write", 2),
                        ("moe_gmm", 2)):
        assert len(re.findall(rf"%{name}[.\d]* = ", hlo)) == calls, name
    whole = f"bf16[5,{blocks},1,{_MLA_BS},640]"
    for line in hlo.splitlines():
        if re.search(r"%latent_decode_attn[.\d]* = ", line):
            operands = line[line.index("operand_layout_constraints="):
                            line.index("output_to_operand_aliasing=")]
            assert operands.count(whole) == 1, operands
    shaped = re.compile(rf"= \(?\w+\[(\d+,)?{blocks},1,{_MLA_BS},640\]")
    moved = [line.strip() for line in hlo.splitlines()
             if shaped.search(line) and not any(f in line for f in _FREE)]
    assert moved == []
    assert f"= bf16[{_MLA_VISIT * _MLA_BS},640]" not in hlo
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.alias_size_in_bytes == cache.k.size * 2


@pytest.mark.parametrize("slots", [1, 48, 256])
def test_gdn_step_lowers(slots):
    """The tick's in-place delta-rule update at Qwen3-Next's widths (32
    value heads of a ``[128, 128]`` float32 state), on the whole
    ``[L, slots, ...]`` cache at a traced layer."""
    from ray_tpu.ops import gated_delta

    f32 = jnp.float32
    fn = functools.partial(gated_delta.gdn_step, use_kernel=True)
    assert gated_delta.gdn_applicable(32, 128, 128)
    row = S((slots, 32, 128), f32)
    gate = S((slots, 32), f32)
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        S((6, slots, 32, 128, 128), f32), S((), jnp.int32), row, row, row,
        gate, gate)
    assert _kernel_names(exported.mlir_module()) == ["gdn_step"]


@pytest.mark.parametrize("carried", [True, False], ids=["carried", "fresh"])
@pytest.mark.parametrize("rows", [1, 8])
def test_gdn_chunk_scan_lowers(rows, carried):
    """The prefill's chunkwise delta rule at Qwen3-Next's widths (32
    value heads over 16 key heads at 128 x 128, 1024 positions in chunks
    of 64, bf16 operands): one Mosaic call, with a carried state and
    from none."""
    from ray_tpu.ops import gated_delta

    f32 = jnp.float32
    fn = functools.partial(gated_delta.gdn_chunked_scan, chunk=64,
                           dtype=BF16, use_kernel=True)
    assert gated_delta.gdn_scan_applicable(32, 16, 128, 128, 64)
    keyed = S((rows, 1024, 16, 128), f32)
    gate = S((rows, 1024, 32), f32)
    args = (keyed, keyed, S((rows, 1024, 32, 128), BF16), gate, gate)
    if carried:
        args += (S((rows, 32, 128, 128), f32),)
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert _kernel_names(exported.mlir_module()) == ["gdn_chunk_scan"]


def test_compiled_linear_tick_holds_no_copy_of_the_state_cache(v5e_chip):
    """The cell ``serve_linear_decode``'s tick, compiled for a described
    v5e at the cell's own sizes (Qwen3-Next's widths, 8 layers = (3
    Gated DeltaNet, 1 full attention) x 2, experts 0-63 of 512, 256 slots
    x 3072 over 12,289 blocks): each linear run's layer loop calls
    ``gdn_step`` once on the WHOLE state cache ``[6, 256, 32, 128, 128]``
    float32 (3.2 GB), aliased in to out, and no other instruction has the
    cache's or one layer's slab's shape (an XLA update in the loop would
    slice 537 MB out and put it back a layer a tick); every run calls
    ``moe_gmm`` twice; the two full-attention runs touch the arena
    through ``paged_kv_write`` and ``paged_decode_attn``; and beside the
    10.5 GB of arguments (4.0 GB of weights, 3.2 GB of arena, 3.3 GB of
    state and conv tails, the last two donated) the program needs under
    32 MB."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import PagedKVCache, StateCache

    slots, bs, width = 256, 64, 48
    cfg = llama.LlamaConfig.qwen3_next_80b_a3b(
        num_layers=8, layer_types=(("linear_attention",) * 3
                                   + ("full_attention",)) * 2,
        vocab_size=18992, experts_held=(0, 64), max_seq_len=bs * width)

    def spec(a):
        return S(a.shape, a.dtype, sharding=v5e_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, 1 + slots * width, bs, kv_dtype="bf16")))
    state = jax.tree.map(spec, jax.eval_shape(functools.partial(
        StateCache.create, cfg, slots)))
    assert cache.k.shape == (2, 12289, 2, 64, 256)
    assert state.ssm.shape == (6, 256, 32, 128, 128)
    assert state.conv.shape == (6, 256, 3, 8192)
    row = S((slots,), jnp.int32, sharding=v5e_chip)
    tables = S((slots, width), jnp.int32, sharding=v5e_chip)
    step = S((), jnp.int32, sharding=v5e_chip)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    compiled = jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, (cache, state), step).compile()
    hlo = compiled.as_text()
    for name, calls in (("gdn_step", 2), ("moe_gmm", 8),
                        ("paged_decode_attn", 2), ("paged_kv_write", 4)):
        assert len(re.findall(rf"%{name}[.\d]* = ", hlo)) == calls, name
    shaped = re.compile(r"= \(?\w+\[(\d+,)?256,32,128,128\]")
    moved = [line.strip() for line in hlo.splitlines()
             if shaped.search(line) and not any(f in line for f in _FREE)]
    assert moved == []
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 32 << 20
    assert memory.alias_size_in_bytes >= state.ssm.size * 4


def _eva_cell(v5e_chip):
    """The cell ``serve_eva_decode``'s sizes as shapes on a described
    v5e: (config, params, arena, table width)."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import eva
    from ray_tpu.models.paged_kv import PagedKVCache

    cfg = llama.LlamaConfig.evabyte_6_5b(num_layers=8, max_seq_len=16384)

    def spec(a):
        return S(a.shape, a.dtype, sharding=v5e_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, 32 * 42 + 1, 64)))
    assert cache.k.shape == (8, 1345, 32, 64, 128)
    return cfg, params, cache, eva.blocks_peak(16384, cfg, 64)


def test_compiled_eva_tick_rewrites_the_arena_in_place(v5e_chip):
    """The cell ``serve_eva_decode``'s tick for a described v5e at the
    cell's own sizes (EvaByte's widths, 8 layers, 32 slots over 1,345
    blocks of 8.4 MB): the layer loop touches the arena through
    ``paged_kv_write`` and ``paged_decode_attn`` alone, unchanged; the
    compression behind it is one more loop in the SAME program (a trip
    a row that closes a window), which holds one window's blocks at a
    time (0.4 GB of scratch beside 14.5 GB of arguments: no copy of the
    11.3 GB arena, which is aliased in to out)."""
    from ray_tpu.models import continuous_batching as cb

    cfg, params, cache, width = _eva_cell(v5e_chip)
    assert width == 2 * 7 + 32
    row = S((32,), jnp.int32, sharding=v5e_chip)
    tables = S((32, width), jnp.int32, sharding=v5e_chip)
    step = S((), jnp.int32, sharding=v5e_chip)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    compiled = jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, cache, step).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 1
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == 2
    # Two loops carry the tables: the layer loop, with the hidden state,
    # and the loop over the rows that close, without it.
    tabled = [line.split(" while(")[0] for line in hlo.splitlines()
              if " while(" in line and "s32[32,46]" in line.split(" while(")[0]]
    assert sorted("f32[32,1,4096]" in loop for loop in tabled) == [False,
                                                                   True]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 512 << 20
    assert memory.alias_size_in_bytes >= 2 * cache.k.size * 2


@pytest.mark.parametrize("earlier", [0, 5])
def test_compiled_eva_prefill_chunk_lands_blocks_in_the_layer_loop(
        v5e_chip, earlier):
    """The cell's worst prefill chunk (4 rows x one 2048-byte window,
    over 0 and over 5 earlier windows' summaries): each layer lands its
    own blocks in the arena it carries, so the program needs under 1 GB
    of scratch (2.6 GB when the layers' raw keys were stacked as the
    loop's output) beside the arena, which is aliased in to out."""
    from ray_tpu.models import continuous_batching as cb

    cfg, params, cache, _ = _eva_cell(v5e_chip)

    def prefill(params, tokens, cache, ptables, tables_w, last_idx):
        positions = earlier * cfg.eva_window + jnp.arange(tokens.shape[1])
        logits, cache, _ = cb._prefill_chunk_paged(
            params, tokens, positions, cache, None, ptables, tables_w,
            last_idx, None, cfg, True)
        return logits, cache

    def ints(*shape):
        return S(shape, jnp.int32, sharding=v5e_chip)

    compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
        params, ints(4, 2048), cache, ints(4, 2 * earlier), ints(4, 32),
        ints(4)).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 30
    assert memory.alias_size_in_bytes >= 2 * cache.k.size * 2


# ------------------------- the CCA cell: one layer, two stores (PR 46)

def _cca_cell(v5e_chip):
    """(config, params, (arena, tail cache)) of the cell
    ``serve_cca_decode`` as shapes on a described v5e."""
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models.paged_kv import PagedKVCache, TailCache

    cfg = llama.LlamaConfig.zaya1_8b(num_layers=10, max_seq_len=7168,
                                     experts_held=(0, 16))

    def spec(a):
        return S(a.shape, a.dtype, sharding=v5e_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        functools.partial(cb.init_engine_params, cfg),
        jax.random.PRNGKey(0)))
    cache = jax.tree.map(spec, jax.eval_shape(functools.partial(
        PagedKVCache.create, cfg, 96 * 112 + 1, 64)))
    tail = jax.tree.map(spec, jax.eval_shape(functools.partial(
        TailCache.create, cfg, 96)))
    assert cache.k.shape == (10, 10753, 2, 64, 128)
    assert tail.tail.shape == (10, 96, 2688)
    return cfg, params, (cache, tail)


def test_compiled_cca_tick_keeps_both_stores_in_place(v5e_chip):
    """The cell ``serve_cca_decode``'s tick for a described v5e at the
    cell's own sizes (ZAYA1-8B's widths, 10 layers, 96 slots over 10,753
    blocks): ONE layer loop that writes the arena through
    ``paged_kv_write`` and reads it through ``paged_decode_attn`` (the
    kernels every attention family uses, at 2 KV heads), advances the
    tail cache beside it and runs the top-1 experts through ``moe_gmm``;
    the 7 GB arena and the tails are aliased in to out, and the scratch
    beside 12.3 GB of arguments stays under half a gigabyte."""
    from ray_tpu.models import continuous_batching as cb

    cfg, params, caches = _cca_cell(v5e_chip)
    row = S((96,), jnp.int32, sharding=v5e_chip)
    tables = S((96, 112), jnp.int32, sharding=v5e_chip)
    step = S((), jnp.int32, sharding=v5e_chip)
    tick = functools.partial(cb._decode_tick_paged, config=cfg,
                             use_kernel=True)
    compiled = jax.jit(tick, donate_argnums=(5,)).lower(
        params, row, row, tables, row, caches, step).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"%paged_decode_attn[.\d]* = ", hlo)) == 1
    assert len(re.findall(r"%paged_kv_write[.\d]* = ", hlo)) == 2
    assert len(re.findall(r"%moe_gmm[.\d]* = ", hlo)) == 2
    memory = compiled.memory_analysis()
    arena = 2 * caches[0].k.size * 2
    assert memory.alias_size_in_bytes >= arena + caches[1].tail.size * 2
    assert memory.temp_size_in_bytes < 512 << 20
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15 << 30)


@pytest.mark.parametrize("rows,earlier", [(8, 0), (8, 48), (1, 48)])
def test_compiled_cca_prefill_chunk_fits_beside_the_arena(v5e_chip, rows,
                                                          earlier):
    """The cell's prefill chunks (1 and 8 rows x 1024 tokens, a prompt's
    first and its fourth, over 48 earlier blocks): K/V through the
    arena, the tails through rows ``slots`` of the tail cache, both
    aliased in to out; arguments and scratch fit the chip."""
    from ray_tpu.models import continuous_batching as cb

    cfg, params, (cache, tail) = _cca_cell(v5e_chip)

    def prefill(params, tokens, cache, tail, ptables, tables_w, last_idx,
                slots):
        positions = earlier * 64 + jnp.arange(tokens.shape[1])
        return cb._prefill_chunk_paged(
            params, tokens, positions, cache, tail, ptables, tables_w,
            last_idx, slots, cfg, True)

    def ints(*shape):
        return S(shape, jnp.int32, sharding=v5e_chip)

    compiled = jax.jit(prefill, donate_argnums=(2, 3)).lower(
        params, ints(rows, 1024), cache, tail, ints(rows, earlier),
        ints(rows, 16), ints(rows), ints(rows)).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * cache.k.size * 2
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15 << 30)


# ------------------------------------- the FSDP train step's collectives

_HEAD_COLLECTIVE = re.compile(
    r" = \(?(\w+)\[(?:\d+,)+(?:32768|16384)\][^=]* "
    r"(all-gather|all-reduce|reduce-scatter)(?:-start)?\(.*channel_id=(\d+)")
_NORM_GATHER = re.compile(
    r" = \(?\w+\[(?:1,)?4096\][^=]* all-gather(?:-start)?\(")


def _loops(hlo_text: str):
    """``(outside, loops)`` of compiled HLO: the lines no ``while`` body
    reaches, and for each ``while`` the lines of its body and of every
    computation the body calls (fusions and nested loops included)."""
    bodies, name = {}, None
    for line in hlo_text.splitlines():
        opened = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if opened and not line.startswith(" "):
            name = opened.group(1)
            bodies[name] = []
        elif name is not None and not line.startswith("}"):
            bodies[name].append(line)

    def reach(start, seen):
        if start in bodies and start not in seen:
            seen.add(start)
            for line in bodies[start]:
                for called in re.findall(
                        r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                        line):
                    reach(called, seen)
        return seen

    reached = [reach(m.group(1), set())
               for lines in bodies.values() for line in lines
               if " while(" in line
               for m in [re.search(r"body=%?([\w.\-]+)", line)]]
    inside = set().union(*reached)
    outside = [line for c, lines in bodies.items() if c not in inside
               for line in lines]
    return outside, [[line for c in sorted(loop) for line in bodies[c]]
                     for loop in reached]


@pytest.mark.parametrize("fsdp,tensor", [(4, 1), (2, 2)])
def test_compiled_fsdp_step_gathers_what_its_loops_reuse_once(
        v5e_host, fsdp, tensor):
    """``train_fsdp4``'s step at Mistral-7B widths (two layers: the loops
    are the same), compiled for the four described chips. Every chunk of
    the loss scan needs the whole head and every layer a norm weight, so
    the head is gathered over ``fsdp`` ONCE a step and its gradient
    summed across chips once, and the norm weights are never sharded:

    * no collective on the head stands in a loss-chunk loop (the parent
      gathered ``bf16[4096,32768]`` in the forward and in the backward
      loop and summed its gradient as ``f32[4096,32768]`` in the
      backward loop: 14 gathers and 7 float32 reduces a step);
    * the program has one gather of the head and one reduce of its
      gradient, both begun and ended outside every loop. (The compiler
      may spread the one gather under the forward LAYER loop as pieces
      of an async collective: same channel, started in the entry.);
    * no float32 collective on the head anywhere, ``kCustom`` fusions
      included;
    * no ``all-gather`` of a norm weight in any loop (the parent: 11 in
      a forward layer, 26 in a backward one). Their gradients' one
      ``all-reduce (bf16[4096], bf16[4096])`` a backward layer stays.

    On ``fsdp=2 x tensor=2`` the vocabulary stays on ``tensor``: the
    head is gathered as ``[4096,16384]``, never as ``[*,32768]``."""
    from benchmark import manifest

    cell = manifest.cell("train_fsdp4")
    rows, tokens = (cell["traffic"][k]
                    for k in ("batch_sequences", "sequence_tokens"))
    config = manifest.llama_config(
        dict(cell["config"], num_hidden_layers=2), max_seq_len=tokens,
        remat=True, remat_policy=cell["workload"]["remat_policy"])
    mesh = make_mesh(MeshConfig(fsdp=fsdp, tensor=tensor), devices=v5e_host)
    trainer = ShardedTrainer(config, mesh)
    state = jax.tree.map(
        lambda a, sharding: S(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(trainer._init._jitted, jax.random.PRNGKey(0)),
        trainer.state_shardings)
    batch = {k: S((rows, tokens), jnp.int32, sharding=trainer.batch_sharding)
             for k in ("tokens", "mask")}
    with mesh:
        hlo = trainer._step._jitted.lower(state, batch).compile().as_text()
    outside, loops = _loops(hlo)
    assert len(loops) >= 4           # loss and layers, forward and backward

    def head_collectives(lines):
        return [m.groups() for line in lines
                for m in [_HEAD_COLLECTIVE.search(line)] if m]

    for loop in loops:
        if any("loss_head" in line for line in loop):
            assert head_collectives(loop) == []
        assert [line for line in loop if _NORM_GATHER.search(line)] == []
    begun = set(head_collectives(outside))
    everywhere = set(head_collectives(hlo.splitlines()))
    assert everywhere == begun, "a collective on the head lives in a loop"
    assert sorted((dtype, op) for dtype, op, _ in begun) == [
        ("bf16", "all-gather"), ("bf16", "all-reduce")]
    if tensor > 1:
        assert not re.search(r",32768\][^=]* all-gather", hlo)


# --------------------------------- Mamba-1 selective scan (Jamba, PR 55)

_M1_N, _M1_D = 16, 5120          # Jamba2-3B: states a channel, channels


def _m1_step_specs(slots, spec=S):
    f32 = jnp.float32
    return (spec((26, slots, _M1_N, _M1_D), f32), spec((), jnp.int32),
            spec((slots, _M1_D), BF16), spec((slots, _M1_D), f32),
            spec((_M1_N, _M1_D), f32), spec((slots, _M1_N), f32),
            spec((slots, _M1_N), f32))


def _m1_scan_specs(rows, s, carried, spec=S):
    f32 = jnp.float32
    args = (spec((rows, s, _M1_D), BF16), spec((rows, s, _M1_D), f32),
            spec((_M1_N, _M1_D), f32), spec((rows, s, _M1_N), f32),
            spec((rows, s, _M1_N), f32))
    return args + ((spec((rows, _M1_N, _M1_D), f32),) if carried else ())


@pytest.mark.parametrize("slots", [4, 256])
def test_mamba1_step_lowers(slots):
    """The tick's in-place selective-scan update at Jamba2-3B's widths
    (a ``[16, 5120]`` float32 state a slot), on the whole ``[26, slots,
    ...]`` cache at a traced layer: one block of few slots, and blocks
    of eight."""
    from ray_tpu.ops import selective_scan

    fn = functools.partial(selective_scan.mamba1_step, use_kernel=True)
    assert selective_scan.step_applicable(slots, _M1_N, _M1_D)
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *_m1_step_specs(slots))
    assert _kernel_names(exported.mlir_module()) == ["mamba1_step"]


@pytest.mark.parametrize("carried", [True, False], ids=["carried", "fresh"])
@pytest.mark.parametrize("rows,s", [(16, 512), (1, 1024), (1, 16)])
def test_mamba1_scan_lowers(rows, s, carried):
    """The prefill's scan over positions at Jamba2-3B's widths: the
    mix's bucket, a whole chunk, and a second chunk of ONE token (padded
    to 16), with a carried state and from none."""
    from ray_tpu.ops import selective_scan

    fn = functools.partial(selective_scan.mamba1_scan, use_kernel=True)
    assert selective_scan.scan_applicable(s, _M1_N, _M1_D)
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *_m1_scan_specs(rows, s, carried))
    assert _kernel_names(exported.mlir_module()) == ["mamba1_scan"]


@pytest.mark.parametrize("kernel", ["mamba1_step", "mamba1_scan",
                                    "paged_decode_attn", "paged_kv_write"])
def test_compiled_jamba_kernels_for_the_described_chip(v5e_chip, kernel):
    """A real compile (the exports above stop before Mosaic) of what the
    Jamba2-3B tick and prefill call: the two selective-scan kernels at
    the cell's 256 slots and 16 x 512 rows, and the paged kernels at 20
    query heads on ONE K/V head: a query group of 20 rows, which is no
    multiple of the 8-row sublane tile, and a one-head arena."""
    from ray_tpu.ops import selective_scan

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    arena = on_chip((2, 641, 1, 64, 128), BF16)
    if kernel == "mamba1_step":
        fn = functools.partial(selective_scan.mamba1_step, use_kernel=True)
        specs = _m1_step_specs(256, on_chip)
    elif kernel == "mamba1_scan":
        fn = functools.partial(selective_scan.mamba1_scan, use_kernel=True)
        specs = _m1_scan_specs(16, 512, True, on_chip)
    elif kernel == "paged_decode_attn":
        def fn(q, k, v, tables, pos):
            return paged_decode_attention(
                q, k, v, tables, pos, layer=jnp.int32(1), limits=pos,
                use_kernel=True, interpret=False)
        specs = (on_chip((16, 20, 128), BF16), arena, arena,
                 on_chip((16, 40), jnp.int32), on_chip((16,), jnp.int32))
    else:
        fn = paged_kv_write
        where = on_chip((16, 1), jnp.int32)
        specs = (arena, on_chip((16, 1, 1, 128), BF16),
                 on_chip((), jnp.int32), where, where)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert len(re.findall(rf"%{kernel}[.\d]* = ",
                          compiled.as_text())) == 1
