#!/usr/bin/env python3
"""Does ray_tpu still start on the chip? The quickest end-to-end proof.

    python3 chip_smoke.py                # the real run: needs a TPU
    python3 chip_smoke.py --rehearse     # CPU, tiny model, kernels interpreted

Drives the two main paths once, through the entry points a user calls, at
the full width of the 953M Llama-shaped config both benches use (hidden
2048, 16 layers, 16 heads of 128, vocab 32,000; random weights from a
seed), and checks what comes out by the repo's own means:

* ``kernels``   flash attention forward/backward (at the two train
                cells' shapes; each kernel's ms a call, its block-step
                schedule and its TFLOP/s over the causal work, PR 56)
                and paged decode
                attention (bf16 and int8 arena), COMPILED, against their
                references within the tolerances in ``TOLERANCE``; the
                routed block's grouped matmul (``moe_gmm``) against a
                masked loop over the experts; ``paged_decode_attn``
                against the walk over every table entry it replaced
                (PR 26: one block a step bit for bit, a wider step's
                one softmax chain within a bf16 ulp, PR 47), and both
                timed alone at the serve cells' shapes and fill, at
                1 to 16 blocks a step; ``gdn_step`` against the
                ``jax.numpy`` step at Qwen3-Next's head shape, timed;
* ``moe``       the OLMoE family's bf16 forward against the float32
                reference at the published widths, and three faults
                (an expert dropped, weights renormalised, no QK-norm)
                shown to land outside the benchmark's tolerances;
* ``hybrid``    the Granite 4.0-H family at the published widths (two
                Mamba-2 layers and an attention layer): a padded,
                multi-chunk bf16 prefill, then 300 ticks through the
                state cache, every position's logits against the float32
                reference's full forward; six faults shown to land
                outside the configuration file's tolerances, and a bf16
                state measured beside them;
* ``window``    the cell ``serve_window_decode``'s comparison at its
                own sizes (Trinity's share at the published widths, five
                layers, through ``ContinuousBatcher``): the cell's check
                prompts, chosen tokens and kept routes against the
                float32 reference under the configuration file's two
                limits; the program as published passes, and seven
                faults (bf16 router scores and a dropped selection bias
                among them) each fail;
* ``linear``    the cell ``serve_linear_decode``'s comparison at its
                own sizes (Qwen3-Next's share at the published widths,
                eight layers, through ``ContinuousBatcher``): check
                prompts that cross the prefill chunk boundary and do
                not, chosen tokens and kept routes against the float32
                reference under the configuration file's two limits;
                the program as published passes, eleven faults (state
                or conv tail not carried between chunks among them)
                each fail, and a bf16 recurrent state is read; before
                them the prefill's ``gdn_chunk_scan`` kernel against the
                ``jax.numpy`` scan at the published shape, its solve
                against a float64 one, and both forms' time a layer;
* ``eva``       the cell ``serve_eva_decode``'s comparison at its own
                sizes (EvaByte's stage at the published widths, eight
                layers, through ``ContinuousBatcher``): check prompts
                inside a window, on its last position, filling it, one
                past it, closing one in a tick, and across three; chosen
                bytes against the float32 reference under the
                configuration file's limit; the program as published
                passes, and five faults in the program and three in the
                reference are read against it;
* ``loop``      the cell ``serve_loop_decode``'s comparison at its own
                sizes (Ouro-2.6B WHOLE at the published widths, 48
                layers applied 4 times, through ``ContinuousBatcher``
                with the cell's 8 slots and 89 blocks): the cell's
                check prompts, chosen tokens against the float32
                reference under the configuration file's limit, and
                the exit gate after each step against the reference's;
                the program as published passes (two sets of prompts),
                float8 weights and six faults are read against it;
* ``jamba``     the cell ``serve_ssm_decode``'s comparison at its own
                sizes (AI21-Jamba2-3B WHOLE at the published widths, 26
                Mamba-1 mixers and 2 MQA 20/1 layers, through
                ``ContinuousBatcher`` with the cell's 256 slots): the
                cell's check prompts (one with a second chunk of ONE
                token), chosen tokens against the float32 reference
                under the configuration file's limit; the program as
                published passes (two sets of prompts), float8 weights
                and five faults are read against it;
* ``train``     ``ShardedTrainer`` on one device, batch 5 x 2048: loss
                finite and falling, one compiled signature, the Mosaic
                custom calls present in the compiled step;
* ``serve``     ``ray_tpu.init()`` -> ``serve.run(build_continuous_llama_
                app(...))`` -> ``serve.start_http()`` -> concurrent HTTP
                requests, unary and streamed, over two prefill buckets;
* ``multichip`` (four or more devices; otherwise printed as skipped) the
                train step on an ``fsdp=4`` mesh against the one-chip loss,
                and four one-chip replicas behind the router.

This parent process never initialises JAX: a process that has touched JAX
holds the chip, and a child that needs it then fails or hangs. Each phase
is a child that owns the chip for its duration, so a failing phase is
named and cannot leave the chip held. Any phase failing fails the run:
the exit code is non-zero and no result line is printed. On success the
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--rehearse`` runs the same control flow at ``LlamaConfig.tiny()`` under
``JAX_PLATFORMS=cpu`` with Pallas kernels in interpret mode. It says so in
its output, proves nothing about the chip, and is what to run before
spending chip time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

PHASES = ("kernels", "moe", "hybrid", "window", "mla", "linear", "eva",
          "cca", "loop", "jamba", "train", "serve", "multichip")
# The multichip phase is two children: the trainer's state must be gone
# from the chips before four serving replicas load theirs.
CHILDREN = {"kernels": ("kernels",), "moe": ("moe",),
            "hybrid": ("hybrid",), "window": ("window",), "mla": ("mla",),
            "linear": ("linear",), "eva": ("eva",), "cca": ("cca",),
            "loop": ("loop",), "jamba": ("jamba",), "train": ("train",),
            "serve": ("serve",),
            "multichip": ("multichip-train", "multichip-serve")}
PHASE_TIMEOUT_S = {"kernels": 1200, "moe": 600, "hybrid": 1500,
                   "window": 2700, "mla": 2700, "linear": 3300, "eva": 3300,
                   "cca": 3300, "loop": 3300, "jamba": 3300,
                   "train": 480,
                   "serve": 600,
                   "multichip-train": 900, "multichip-serve": 900}
RESULT_TAG = "PHASE_RESULT "
NO_ACCELERATOR_RC = 3    # a child found no TPU: no later phase can pass
REHEARSAL_BANNER = (
    "REHEARSAL: LlamaConfig.tiny() on JAX_PLATFORMS=cpu with Pallas "
    "kernels INTERPRETED. This checks control flow only and says nothing "
    "about the chip.")

# Largest |kernel - reference| allowed, as a fraction of the reference's
# largest magnitude. Inputs and outputs are bf16 (8 significand bits:
# one rounding is 2^-9 relative); the references run in fp32 at
# matmul precision "highest" on the same bf16 inputs. Gradients pass
# through two more bf16 roundings (dO, and the recomputed P) than the
# forward. The chip run prints what was measured next to each bound.
TOLERANCE = {"flash_fwd": 1e-2, "flash_bwd": 2e-2,
             "decode_bf16": 1e-2, "decode_int8": 1e-2, "moe_gmm": 1e-2,
             "latent_decode": 1e-2, "gdn_step": 1e-5,
             # Kernel and jax.numpy scan round the same bf16 operands; a
             # float32 last bit (another exp, another order of addition)
             # flips one rounding now and then: one bf16 ulp, 2^-8.
             "gdn_scan": 2.0 ** -8, "gdn_scan_solve": 2e-5,
             # A visit of several blocks folds them in one softmax chain
             # where the one-block walk folds one after the other: the
             # same float32 arithmetic in another order of rounding, so a
             # bf16 output now and then one ulp (2^-8) off the walk's.
             "paged_visit": 2.0 ** -7}
# fsdp=4 vs one-chip first-step loss: the same bf16 model, sums reduced
# across four devices in another order.
LOSS_RTOL = 5e-3
MIN_CHIPS_MULTI = 4


# ---------------------------------------------------------------- parent
def _run_child(phase: str, rehearse: bool, env: dict):
    """Run one phase in its own process group; echo its output; return
    (exit code, result dict or None)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if rehearse:
        cmd.append("--rehearse")
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(PHASE_TIMEOUT_S[phase], kill_group)
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        kill_group()   # the phase is over: nothing it started survives it
    return rc, result


def _parent(phases, rehearse: bool) -> int:
    env = dict(os.environ)
    if rehearse:
        print(REHEARSAL_BANNER, flush=True)
        env["JAX_PLATFORMS"] = "cpu"
        env["RAY_TPU_PALLAS_INTERPRET"] = "1"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + ["--xla_force_host_platform_device_count=8"])
    device = None
    failed = []
    t_all = time.monotonic()
    for phase in phases:
        if phase == "multichip" and device is not None \
                and device["count"] < MIN_CHIPS_MULTI:
            print(f"[multichip] skipped: {device['count']} device(s) "
                  f"present, {MIN_CHIPS_MULTI} needed", flush=True)
            continue
        for child in CHILDREN[phase]:
            t0 = time.monotonic()
            rc, result = _run_child(child, rehearse, env)
            dt = time.monotonic() - t0
            ok = rc == 0 and result is not None and result.get("ok")
            print(f"[{child}] {'PASSED' if ok else 'FAILED'} in {dt:.0f}s "
                  f"(exit code {rc})", flush=True)
            if not ok:
                failed.append(child)
                if rc == NO_ACCELERATOR_RC:
                    print("[chip_smoke] FAILED: no accelerator", flush=True)
                    return 1
                continue
            device = device or result["device"]
    print(f"[chip_smoke] total {time.monotonic() - t_all:.0f}s", flush=True)
    if failed or device is None:
        print(f"[chip_smoke] FAILED: {', '.join(failed) or 'no phase ran'}",
              flush=True)
        return 1
    out = {"ok": True, "device": device}
    if rehearse:
        out["rehearsal"] = True
        print(REHEARSAL_BANNER)
    print(json.dumps(out), flush=True)
    return 0


# --------------------------------------------------------------- children
def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _open_device(phase: str, rehearse: bool) -> dict:
    """First touch of JAX in a child: place the compile cache, report the
    device, and refuse to go on without an accelerator."""
    import jax

    from ray_tpu.util import compile_cache

    cache_dir = compile_cache.ensure()
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    _say(phase, f"platform: {info['platform']}, device_kind: "
                f"{info['kind']}, devices: {info['count']}")
    _say(phase, f"compile cache: {cache_dir}")
    if rehearse:
        _say(phase, REHEARSAL_BANNER)
        if info["platform"] != "cpu":
            raise SystemExit(f"[{phase}] --rehearse must run on the CPU "
                             f"backend, got {info['platform']}")
    elif info["platform"] != "tpu":
        _say(phase, f"no accelerator: JAX reports platform "
                    f"{info['platform']!r}; this check only counts on a "
                    "TPU (use --rehearse for a CPU dry run)")
        sys.exit(NO_ACCELERATOR_RC)
    return info


def _finish(phase: str, info: dict, **extra) -> None:
    from ray_tpu.util import compile_cache

    counts = compile_cache.counts()
    _say(phase, f"persistent compile cache: {counts['hits']} hits, "
                f"{counts['misses']} misses")
    print(RESULT_TAG + json.dumps(
        {"ok": True, "device": info, "cache": counts, **extra}), flush=True)


def _full_config(**kw):
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=2048, **kw)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _check(phase: str, name: str, got, ref, bound: float) -> None:
    import numpy as np

    assert got.shape == ref.shape and got.dtype == ref.dtype, name
    same = bool(np.array_equal(np.asarray(got), np.asarray(ref)))
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    _say(phase, f"{name}: max|kernel-ref|/max|ref| = {err:.2e} "
                f"(bound {bound:.0e}); bit-identical: {same}")
    assert err <= bound, f"{name}: {err:.3e} exceeds {bound:.0e}"


def _same(phase: str, name: str, got, ref) -> None:
    import numpy as np

    assert got.shape == ref.shape and got.dtype == ref.dtype, name
    diff = int(np.sum(np.asarray(got) != np.asarray(ref)))
    _say(phase, f"{name}: {diff} of {got.size} elements differ")
    assert diff == 0, f"{name}: not bit-identical"


def walk_every_entry(q, arena_k, arena_v, tables, positions, *, layer=None,
                     k_scale=None, v_scale=None, window=0):
    """``paged_decode_attn`` as it was before PR 26, kept as the
    yardstick: grid ``(slots, table entries)``, every entry a grid step
    and a dead one skipped by ``pl.when``. A live slot's blocks go
    through ``_block_scores`` and ``_fold_blocks`` one after the other,
    which is ``paged_decode_attention``'s arithmetic at one block a
    grid step,
    so there its row must come out the same bits (a wider step folds
    its blocks in one chain: another order of rounding); a freed slot
    attends the garbage block at position 0. With ``window``
    the table is a ring and entry ``j`` of the walk is the slot's ``j``-th
    block from its first live one (ring entry ``block % nb``), the ones
    past its position skipped. Arguments as ``paged_decode_attention``'s."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ray_tpu.ops.dispatch import interpret_default
    from ray_tpu.ops.paged_decode_attention import (_block_scores, _finalize,
                                                    _first_live,
                                                    _fold_blocks, _init_state,
                                                    _scratch, _store_state,
                                                    pltpu)

    if layer is None:
        layer = 0
        arena_k, arena_v = arena_k[None], arena_v[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    b, hq, d = q.shape
    hkv, bs = arena_k.shape[2], arena_k.shape[3]
    nb, group = tables.shape[1], hq // hkv
    quantized = k_scale is not None

    def kernel(layer_ref, tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest):
        if quantized:
            ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
        else:
            o_ref, acc_ref, m_ref, l_ref = rest
        pos = pos_ref[pl.program_id(0)]
        j = _first_live(pos, window, bs) + pl.program_id(1)

        @pl.when(pl.program_id(1) == 0)
        def _init():
            _init_state(acc_ref, m_ref, l_ref)

        @pl.when(j * bs <= pos)
        def _body():
            s = _block_scores(q_ref[0], k_ref[0, 0], pos, j * bs,
                              scale=d ** -0.5, window=window,
                              k_scale=ks_ref[0, 0] if quantized else None)
            _store_state(_fold_blocks(
                [s], m_ref[:, :, :1], l_ref[:, :, :1], acc_ref[:],
                [v_ref[0, 0]], [vs_ref[0, 0] if quantized else None]),
                acc_ref, m_ref, l_ref)

        @pl.when(pl.program_id(1) == nb - 1)
        def _fin():
            _finalize(o_ref, acc_ref, l_ref)

    def entry(b_, j, tab, po):
        return tab[b_, (_first_live(po[b_], window, bs) + j) % nb]

    q_spec = pl.BlockSpec((1, hkv, group, d),
                          lambda b_, j, ly, tab, po: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, hkv, bs, d),
        lambda b_, j, ly, tab, po: (ly[0], entry(b_, j, tab, po), 0, 0, 0))
    sc_spec = pl.BlockSpec(
        (1, 1, hkv, bs),
        lambda b_, j, ly, tab, po: (ly[0], entry(b_, j, tab, po), 0, 0))
    scales = [k_scale, v_scale] if quantized else []
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nb),
            in_specs=[q_spec, kv_spec, kv_spec] + [sc_spec] * len(scales),
            out_specs=q_spec, scratch_shapes=_scratch(hkv, group, d)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret_default(), name="paged_walk_every_entry",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      positions.astype(jnp.int32), q.reshape(b, hkv, group, d), arena_k,
      arena_v, *scales)
    return out.reshape(b, hq, d)


# The paged kernel alone at each serve cell's shapes and fill: (cell,
# slots, table entries, q heads, kv heads, layers, arena blocks, live
# blocks of each live slot, the layers' sliding window (0: a table), the
# head size, what a call took in that cell's trace). Slots without
# blocks are freed, as most of serve_chat's are at its arrival rate. The
# window cell's two shapes: a window layer's 66-entry rings with 65
# blocks in the window, and the full layer's 112-entry tables at
# 4.9k-6.2k keys (two layers where the cell has one: a call at a
# constant layer index is hoisted out of the loop that times it). The
# two arenas of 2 kv heads (PR 47): ZAYA1's at 2,049-6,400 keys a slot
# (a 64 KB block: sixteen a grid step) and Qwen3-Next's two attention
# layers at head size 256 (131 KB: eight).
PAGED_CELLS = (
    ("serve_chat", 48, 32, 32, 8, 16, 1000, {6 * i: 6 for i in range(8)},
     0, 128, "the every-entry walk 209 us (PR 24)"),
    ("serve_prefill_heavy", 8, 18, 32, 8, 16, 145,
     {i: 13 for i in range(8)}, 0, 128, "the every-entry walk 71 us (PR 24)"),
    ("serve_moe_decode", 48, 16, 16, 16, 12, 512,
     {i: 4 + i % 2 for i in range(48)}, 0, 128,
     "the every-entry walk 292 us (PR 25)"),
    ("serve_window_decode ring", 48, 66, 48, 8, 4, 1 + 48 * 66,
     {i: 65 for i in range(48)}, 4096, 128,
     "one block a step 1960 us (PR 32)"),
    ("serve_window_decode table", 48, 112, 48, 8, 2, 1 + 48 * 112,
     {i: 77 + (7 * i) % 20 for i in range(48)}, 0, 128,
     "one block a step 2570 us (PR 32)"),
    ("serve_cca_decode", 96, 112, 8, 2, 10, 1 + 96 * 100,
     {i: 33 + (7 * i) % 68 for i in range(96)}, 0, 128,
     "four blocks a step 1232 us (PR 46)"),
    ("serve_linear_decode", 256, 48, 16, 2, 2, 1 + 256 * 32,
     {i: 17 + (5 * i) % 16 for i in range(256)}, 0, 256,
     "four blocks a step, 0.157 s of a 4 s capture a layer (PR 46)"),
)
PAGED_REHEARSAL_CELLS = (
    ("tiny", 4, 4, 4, 2, 2, 17, {0: 2, 2: 4}, 0, 128, None),
    ("tiny ring", 4, 5, 4, 2, 2, 21, {i: 4 for i in range(4)}, 100, 128,
     None),
    # A full step of sixteen, a short one behind it, and a slot whose
    # only visit is short.
    ("tiny long", 4, 20, 4, 2, 2, 36, {0: 17, 1: 16, 3: 1}, 0, 128, None))
# Blocks a grid step, timed side by side (the module ships one: the
# arena's ``visit_blocks``; a row is timed up to twice its own).
PAGED_VISIT_BLOCKS = (1, 2, 3, 4, 8, 16)
PAGED_REHEARSAL_VISIT_BLOCKS = (1, 3, 16)


def _paged_cell_inputs(slots, nb, hq, hkv, layers, blocks, live, window,
                       bs, d):
    """One cell's kernel inputs: (q, [arena_k, arena_v], tables,
    positions, limits). Live slots own their blocks alone and stand
    near the end of their last one (over a ring: where ``live`` blocks
    hold a key of the window, some wraps in); the others are freed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.split(jax.random.PRNGKey(26), 3)
    arena = [jax.random.normal(k, (layers, blocks, hkv, bs, d),
                               jnp.bfloat16) for k in key[:2]]
    q = jax.random.normal(key[2], (slots, hq, d), jnp.bfloat16)
    tables = np.zeros((slots, nb), np.int32)
    positions = np.zeros(slots, np.int32)
    limits = np.zeros(slots, np.int32)
    ids = iter(range(1, blocks))
    for slot, n in live.items():
        if window:
            # The whole ring is the slot's, and the query stands some
            # wraps in, where ``n`` = window // bs + 1 blocks hold a key
            # of its window: the newest block from a row at which the
            # window's first key has not left block ``first``.
            tables[slot] = [next(ids) for _ in range(nb)]
            assert n == window // bs + 1, (n, window, bs)
            first, lo = nb + slot % nb, max(window % bs - 1, 0)
            positions[slot] = ((first + n - 1) * bs + lo
                               + slot % (bs - 1 - lo))
            limits[slot] = nb * bs
            continue
        # A dead tail repeats the last live block (`_table_row`).
        row = [next(ids) for _ in range(n)]
        tables[slot] = row + [row[-1]] * (nb - n)
        positions[slot] = n * bs - 1 - slot % bs
        limits[slot] = n * bs
    return (q, arena) + tuple(map(jnp.asarray, (tables, positions, limits)))


def _time_us(call, q, arena, layers: int, reps: int) -> float:
    """Microseconds a call of ``call(q, k, v, layer)``: ``reps`` calls
    inside one program (a call dispatched by itself is mostly
    dispatch), the layer index turning with the call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(q, k, v):
        def body(i, acc):
            return acc + call(q, k, v, i % layers).astype(jnp.float32)
        return jax.lax.fori_loop(0, reps, body,
                                 jnp.zeros(q.shape, jnp.float32))

    loop(q, *arena).block_until_ready()
    t0 = time.perf_counter()
    loop(q, *arena).block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def _kernel_ms(call, args, names, reps: int = 6) -> dict:
    """Median device milliseconds a call of each Pallas kernel in
    ``names`` over ``reps`` runs of ``call(*args)``, from a profiler
    trace's ``XLA Ops`` line (a kernel's event carries its ``name=``).
    Empty where there is no device trace (the rehearsal)."""
    import tempfile

    import jax

    from benchmark import trace_reduce

    jax.block_until_ready(call(*args))
    got: dict = {}
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir):
            for _ in range(reps):
                out = call(*args)
            jax.block_until_ready(out)
        path = trace_reduce.find(log_dir)
        for lines in (trace_reduce.load(path) if path else {}).values():
            for event, _, ns in lines.get(trace_reduce.OPS_LINE, []):
                head = event.split(" = ")[0]
                for name in names:
                    if name in head:
                        got.setdefault(name, []).append(ns / 1e6)
    return {k: sorted(v)[len(v) // 2] for k, v in got.items()}


def _time_paged_cells(phase: str, device_kind: str, rehearse: bool) -> None:
    """Time ``paged_decode_attn`` at each count of blocks a grid step
    (``PAGED_VISIT_BLOCKS`` up to twice the arena's ``visit_blocks``,
    the one shipped) and the walk it replaced, each alone, beside the
    time the live blocks' bytes take at the device's HBM peak. One block
    a step must give the walk's rows bit for bit; a wider step folds its
    blocks in one softmax chain, another order of rounding, and is held
    to ``TOLERANCE["paged_visit"]`` of the walk. The schedule is made
    once, outside the loop, as the engine makes it."""
    import jax
    import jax.numpy as jnp

    from benchmark import peaks
    from ray_tpu.ops.paged_decode_attention import (paged_decode_attention,
                                                    paged_visits,
                                                    visit_blocks)

    bs, reps = (32, 4) if rehearse else (64, 1024)
    # The rehearsal's CPU has no peak on record, and no time to compare.
    hbm = None if rehearse else peaks.for_device(device_kind)[
        "hbm_bytes_per_s"]
    for cell, *shape, d, was in (PAGED_REHEARSAL_CELLS if rehearse
                                 else PAGED_CELLS):
        slots, nb, _, hkv, layers, _, live, window = shape
        q, arena, tables, positions, limits = _paged_cell_inputs(
            *shape, bs, d)
        n_live = sum(live.values())
        shipped = visit_blocks(arena[0])
        what = (f"paged_decode_attn alone, {cell} ({slots} x {nb} entries, "
                f"{hkv} kv heads of {d}, {n_live} live, "
                f"{slots - len(live)} slots freed)")

        def walk(q, k, v, li):
            return walk_every_entry(q, k, v, tables, positions, layer=li,
                                    window=window)

        want = jnp.where((limits == 0)[:, None, None], 0,
                         jax.jit(walk)(q, *arena, layers - 1))
        took = {}
        for per in (PAGED_REHEARSAL_VISIT_BLOCKS if rehearse
                    else PAGED_VISIT_BLOCKS):
            if per > 2 * shipped:
                continue
            visits = paged_visits(tables, positions, limits, block_size=bs,
                                  per_visit=per, window=window)
            assert int(visits[3][0]) == sum(
                -(-n // per) for n in live.values()), (cell, per)

            def call(q, k, v, li):
                return paged_decode_attention(
                    q, k, v, tables, positions, layer=li, visits=visits,
                    use_kernel=True, window=window)

            name = (f"{what}, {per} block(s) a step against the "
                    "every-entry walk")
            got = jax.jit(call)(q, *arena, layers - 1)
            if per == 1:
                _same(phase, name, got, want)
            else:
                _check(phase, name, got, want, TOLERANCE["paged_visit"])
            took[per] = _time_us(call, q, arena, layers, reps)
        old_us = _time_us(walk, q, arena, layers, reps)
        if rehearse:
            _say(phase, f"{what}: both kernels ran; a rehearsal times "
                        "nothing")
            continue
        live_bytes = n_live * 2 * hkv * bs * d * 2    # K and V, bf16
        bytes_us = live_bytes / hbm * 1e6
        each = ", ".join(
            f"{us:.1f} us at {per} ({bytes_us / us * 100:.0f}%)"
            + (" (shipped)" if per == shipped else "")
            for per, us in took.items())
        _say(phase, f"{what}: a call by blocks a grid step, and the live "
                    f"bytes' time as a share of it: {each}; every-entry "
                    f"walk {old_us:.1f} us here; in the cell's trace "
                    f"{was}; live bytes / {hbm / 1e9:.0f} GB/s = "
                    f"{bytes_us:.1f} us")


def _latent_kernel(phase: str, device_kind: str, rehearse: bool) -> None:
    """``latent_decode_attn`` at Kimi K2's head shape (64 absorbed
    queries a slot of 640 lanes = 512 latent + 64 rope + 64 pad, values
    the first 512; blocks of 64) against the ``jax.numpy`` gather:
    contexts of 1, 63, 64, 4097 and 10240 rows and a freed slot, in a
    whole ``[L, ...]`` cache read at a layer whose blocks that no live
    table entry names hold NaN. Then its time at the cell's load (96
    slots x about 7000 rows) at 8, 12 and 16 blocks a grid step beside
    the time the live rows' bytes take at the device's HBM peak."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import peaks
    from ray_tpu.ops.latent_decode_attention import (
        latent_attention_reference, latent_decode_attention,
        latent_visit_blocks)
    from ray_tpu.ops.paged_decode_attention import paged_visits

    h, w, rank, bs = (4, 128, 32, 8) if rehearse else (64, 640, 512, 64)
    contexts = [1, 7, 8, 33, 80] if rehearse else [1, 63, 64, 4097, 10240]
    nb = -(-max(contexts) // bs)
    key = jax.random.PRNGKey(7)
    scale = 0.14468

    def inputs(slots, lengths, layers):
        k1, k2 = jax.random.split(jax.random.fold_in(key, slots))
        blocks = 1 + slots * nb
        arena = jax.random.normal(k1, (layers, blocks, 1, bs, w),
                                  jnp.float32).astype(jnp.bfloat16)
        q = jax.random.normal(k2, (slots, h, w), jnp.float32
                              ).astype(jnp.bfloat16)
        tables = np.zeros((slots, nb), np.int32)
        for s_, n in enumerate(lengths):
            live = -(-n // bs)
            row = list(range(1 + s_ * nb, 1 + s_ * nb + live))
            tables[s_] = (row + [row[-1]] * (nb - live)) if live else 0
        positions = jnp.asarray([max(n - 1, 0) for n in lengths], jnp.int32)
        limits = jnp.asarray([nb * bs if n else 0 for n in lengths],
                             jnp.int32)
        return q, arena, jnp.asarray(tables), positions, limits

    lengths = contexts + [0]                  # the last slot is freed
    q, arena, tables, positions, limits = inputs(len(lengths), lengths, 2)
    want = jax.jit(lambda q, a: latent_attention_reference(
        q, a, tables, positions, scale, rank=rank, layer=1))(q, arena)
    # The kernel reads the blocks live entries name and no other: the
    # rest (the garbage block, the dead tails') may hold anything.
    named = np.zeros(arena.shape[1], bool)
    for s_, n in enumerate(lengths):
        named[np.asarray(tables)[s_, :-(-n // bs)]] = True
    arena = arena.at[:, ~named].set(jnp.nan)
    got = jax.jit(lambda q, a: latent_decode_attention(
        q, a, tables, positions, scale, rank=rank, layer=jnp.int32(1),
        limits=limits, use_kernel=True))(q, arena)
    assert not np.asarray(got[-1], np.float32).any(), "freed slot not zero"
    _check(phase, f"latent_decode_attn {h} heads x {w} lanes, values "
                  f"{rank}, block {bs}, contexts {contexts}",
           got[:-1], want[:-1], TOLERANCE["latent_decode"])
    if rehearse:
        return
    slots, rows = 96, 7000
    rng = np.random.default_rng(0)
    lengths = [int(n) for n in rng.integers(rows - 1500, rows + 1500, slots)]
    q, arena, tables, positions, limits = inputs(slots, lengths, 1)
    hbm = peaks.for_device(device_kind)["hbm_bytes_per_s"]
    live = sum(lengths)
    for per in (8, 12, 16):
        visits = paged_visits(tables, positions, limits, block_size=bs,
                              per_visit=per)

        def call(q, a, _v, li, visits=visits):
            out = latent_decode_attention(
                q, a, tables, positions, scale, rank=rank, layer=li,
                visits=visits, use_kernel=True)
            return jnp.pad(out, ((0, 0), (0, 0), (0, w - rank)))

        us = _time_us(call, q, (arena, arena), 1, 64)
        _say(phase, f"latent_decode_attn alone, {slots} slots x ~{rows} rows "
                    f"({live} live), {per} block(s) a step"
                    + (" (shipped)" if per == latent_visit_blocks(arena)
                       else "")
                    + f": {us:.0f} us a call; live rows x 1152 B / "
                    f"{hbm / 1e9:.0f} GB/s = {live * 1152 / hbm * 1e6:.0f} us"
                    f" (x 1280 B as stored: {live * 1280 / hbm * 1e6:.0f})")


def _gdn_kernel(phase: str, device_kind: str, rehearse: bool) -> None:
    """``gdn_step`` at Qwen3-Next's head shape (32 value heads of a
    ``[128, 128]`` float32 state) against the ``jax.numpy`` step, in a
    whole ``[L, slots, ...]`` cache updated at a layer, for 1, 48 and 256
    slots: the outputs, the layer's new states, and the other layer
    untouched. Then its time a call beside the time the bytes of
    ``benchmark/flops_gdn.py`` take at the device's HBM peak."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import flops_gdn, peaks
    from ray_tpu.ops import gated_delta

    h, dk, dv = (4, 16, 128) if rehearse else (32, 128, 128)
    shape = {"linear_num_value_heads": h, "linear_num_key_heads": h // 2,
             "linear_key_head_dim": dk, "linear_value_head_dim": dv}
    f32 = jnp.float32
    step = jax.jit(lambda st, *a: gated_delta.gdn_step(
        st, jnp.int32(1), *a, use_kernel=True), donate_argnums=(0,))
    for slots in ((1, 3) if rehearse else (1, 48, 256)):
        k = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(11),
                                                slots), 6)
        state = jax.random.normal(k[0], (2, slots, h, dk, dv), f32)
        def unit(key):      # unit rows, as the mixer's L2 norm leaves q, k
            x = jax.random.normal(key, (slots, h, dk), f32)
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

        q, kk = unit(k[1]) * dk ** -0.5, unit(k[2])
        v = jax.random.normal(k[3], (slots, h, dv), f32)
        g = -jax.random.uniform(k[4], (slots, h), f32, 0.001, 1.6)
        beta = jax.random.uniform(k[5], (slots, h), f32)
        want_o, want = jax.jit(gated_delta.gdn_step_reference)(
            state[1], q, kk, v, g, beta)
        other = np.asarray(state[0])
        if not rehearse:
            assert _mosaic_calls(step.lower(state, q, kk, v, g,
                                            beta).compile()) == 1
        got_o, got = step(state, q, kk, v, g, beta)
        tag = f"gdn_step {slots} slot(s) x {h} heads x [{dk}, {dv}] float32"
        _check(phase, tag + ", outputs", got_o, want_o, TOLERANCE["gdn_step"])
        _check(phase, tag + ", states", got[1], want, TOLERANCE["gdn_step"])
        assert np.array_equal(np.asarray(got[0]), other), "another layer moved"
        if rehearse:
            continue
        reps = 50

        @functools.partial(jax.jit, donate_argnums=(0,))
        def many(st, q, kk, v, g, beta):
            def body(_, carry):
                st, acc = carry
                o, st = gated_delta.gdn_step(st, jnp.int32(1), q, kk, v, g,
                                             beta, use_kernel=True)
                return st, acc + o
            return jax.lax.fori_loop(0, reps, body, (st, jnp.zeros_like(v)))

        st = got
        st, acc = many(st, q, kk, v, g, beta)       # compiles
        acc.block_until_ready()
        t0 = time.perf_counter()
        st, acc = many(st, q, kk, v, g, beta)
        acc.block_until_ready()
        us = (time.perf_counter() - t0) / reps * 1e6
        hbm = peaks.for_device(device_kind)["hbm_bytes_per_s"]
        least = flops_gdn.step_bytes(shape, slots) / hbm * 1e6
        _say(phase, f"{tag}: {us:.0f} us a call; state read and written once "
                    f"and the rows = {flops_gdn.step_bytes(shape, slots) / 1e6:.1f}"
                    f" MB / {hbm / 1e9:.0f} GB/s = {least:.0f} us "
                    f"({100 * least / us:.0f}% of the roofline)")


def _gdn_scan_kernel(phase: str, rehearse: bool) -> None:
    """The prefill's ``gdn_chunk_scan`` kernel at Qwen3-Next's head shape
    (32 value heads over 16 key heads, ``[128, 128]``), bf16 operands, a
    carried state, 1 and 2 rows x 1024: outputs and final states against
    the ``jax.numpy`` scan. Its solve alone (a Mosaic kernel around
    ``_solve_in_place``: float32 products INSIDE a kernel, which the
    interpreter cannot show rounded to one bf16 pass) against a float64
    solve, on random systems and on one whose keys are alike. Then both
    forms' milliseconds a layer at 1, 2, 4 and 8 rows, on the HOST's
    clock over 20 dispatches: each holds a dispatch's overhead (0.2-1
    ms), so the kernel's device time is a trace's to give
    (``gdn_scan_time_share``, PERF.md section 5)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from ray_tpu.models import gated_delta as mixer
    from ray_tpu.ops import gated_delta as gdn
    from ray_tpu.ops.dispatch import interpret_default

    hk, h, s, qn = (2, 4, 128, 64) if rehearse else (16, 32, 1024, 64)
    dk = dv = 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert gdn.gdn_scan_applicable(h, hk, dk, dv, qn)

    def inputs(rows):
        k = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(44),
                                                rows), 6)
        q = mixer._l2norm(jax.random.normal(k[0], (rows, s, hk, dk)
                                            ).astype(bf16))
        kk = mixer._l2norm(jax.random.normal(k[1], (rows, s, hk, dk)
                                             ).astype(bf16))
        v = jax.random.normal(k[2], (rows, s, h, dv)).astype(bf16)
        # A token's decay in 0.2..0.999, as the seeded mixer's.
        g = jnp.log(jax.random.uniform(k[3], (rows, s, h), f32, 0.2, 0.999))
        beta = jax.nn.sigmoid(jax.random.normal(k[4], (rows, s, h)))
        return (q * dk ** -0.5, kk, v, g, beta,
                jax.random.normal(k[5], (rows, h, dk, dv)))

    scan = {kernel: jax.jit(lambda *a, kernel=kernel: gdn.gdn_chunked_scan(
        *a, chunk=qn, dtype=bf16, use_kernel=kernel))
        for kernel in (True, False)}

    def solve(ls, rs):
        def kernel(l_ref, r_ref, x_ref):
            sides = [gdn._row_tiles(r_ref[...])]
            gdn._solve_in_place([gdn._row_tiles(l_ref[...])], sides)
            x_ref[...] = jnp.concatenate(sides[0], axis=0)
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(rs.shape, f32),
            interpret=interpret_default())(ls, rs)

    k = jax.random.split(jax.random.PRNGKey(45), 2)
    rhs = jax.random.normal(k[1], (qn, dv + dk), f32)
    for name, strict in (
            ("random", jnp.tril(jax.random.normal(k[0], (qn, qn), f32), -1)),
            ("keys alike", 0.9 * jnp.tril(jnp.ones((qn, qn), f32), -1))):
        want = np.linalg.solve(
            np.asarray(strict, np.float64) + np.eye(qn),
            np.asarray(rhs, np.float64)).astype(np.float32)
        _check(phase, f"gdn_chunk_scan's solve, {name} [{qn}, {qn}] system "
                      f"x [{qn}, {dv + dk}]", solve(strict, rhs),
               jnp.asarray(want), TOLERANCE["gdn_scan_solve"])
    for rows in ((1,) if rehearse else (1, 2, 4, 8)):
        args = inputs(rows)
        tag = (f"gdn_chunk_scan {rows} row(s) x {s} x {h} heads over {hk} "
               f"x [{dk}, {dv}], bf16 operands, carried state")
        if not rehearse:
            assert _mosaic_calls(scan[True].lower(*args).compile()) == 1
        outs = {kernel: scan[kernel](*args) for kernel in (True, False)}
        if rows <= 2:
            _check(phase, tag + ", outputs", outs[True][0], outs[False][0],
                   TOLERANCE["gdn_scan"])
            _check(phase, tag + ", states", outs[True][1], outs[False][1],
                   TOLERANCE["gdn_scan"])
        if rehearse:
            continue
        ms = {}
        for kernel, fn in scan.items():
            reps = 20
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
            ms[kernel] = (time.perf_counter() - t0) / reps * 1e3
        units = rows * h * (s // qn)
        _say(phase, f"{tag}, host clock over 20 dispatches: kernel "
                    f"{ms[True]:.3f} ms a layer "
                    f"({ms[True] * 1e3 / units:.2f} us a head a chunk), "
                    f"jax.numpy scan {ms[False]:.3f} ms "
                    f"({ms[False] * 1e3 / units:.2f}): the kernel takes "
                    f"{100 * (1 - ms[True] / ms[False]):.0f}% less")


def phase_kernels(rehearse: bool) -> None:
    phase = "kernels"
    info = _open_device(phase, rehearse)
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.paged_kv import quantize_kv
    from ray_tpu.ops.attention import (flash_applicable, flash_attention,
                                       flash_block_steps, mha_reference)
    from ray_tpu.ops.dispatch import interpret_default
    from ray_tpu.ops.paged_decode_attention import (
        decode_attention_reference, paged_attention_reference,
        paged_decode_attention, paged_kv_write)

    if not rehearse:
        assert "RAY_TPU_PALLAS_INTERPRET" not in os.environ, \
            "RAY_TPU_PALLAS_INTERPRET is set: kernels would not compile"
        assert interpret_default() is False
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 12)

    # -- flash attention, forward and backward -----------------------------
    # The two train cells' attention calls: ``train_1chip``'s and
    # ``train_fsdp4``'s a chip, both GQA. Checked against the float32
    # reference, then each kernel timed alone with the schedule
    # ``flash_block_steps`` counts (skipped | interior | diagonal block
    # steps a (batch, head), the share of the run steps' sub-tiles the
    # backward multiplies) and its TFLOP/s over the CAUSAL work: 2, 3 and
    # 4 products of the s (s + 1) / 2 live scores.
    shapes = [(1, 256, 2, 1)] if rehearse else \
        [(4, 2048, 16, 8), (1, 4096, 32, 8)]
    for b, s, h, hkv in shapes:
        d = 128
        q, w = (jax.random.normal(keys[i], (b, s, h, d), jnp.float32)
                .astype(bf16) for i in (0, 3))
        k, v = (jax.random.normal(keys[i], (b, s, hkv, d), jnp.float32)
                .astype(bf16) for i in (1, 2))
        assert flash_applicable(s, s, d)

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True)
                           .astype(jnp.float32) * w.astype(jnp.float32))

        def ref_loss(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True)
                           .astype(jnp.float32) * w.astype(jnp.float32))

        fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        bwd = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
        if not rehearse:
            n_fwd = _mosaic_calls(fwd.lower(q, k, v).compile())
            n_bwd = _mosaic_calls(bwd.lower(q, k, v).compile())
            _say(phase, f"flash: {n_fwd} Mosaic call(s) forward, {n_bwd} in "
                        "the gradient program")
            assert n_fwd >= 1 and n_bwd >= 3
        with jax.default_matmul_precision("highest"):
            ref_out = jax.jit(
                lambda q, k, v: mha_reference(q, k, v, causal=True))(q, k, v)
            ref_g = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
        tag = f"[{b},{s},{h}/{hkv},{d}] bf16"
        _check(phase, f"flash forward {tag}",
               fwd(q, k, v), ref_out, TOLERANCE["flash_fwd"])
        for name, g, rg in zip(("dq", "dk", "dv"), bwd(q, k, v), ref_g):
            _check(phase, f"flash backward {name} {tag}", g, rg,
                   TOLERANCE["flash_bwd"])

        block = min(1024, s)
        skipped, interior, diagonal, share = flash_block_steps(
            s, s, block, block)
        _say(phase, f"flash {tag}: {skipped} | {interior} | {diagonal} block "
                    f"steps skipped | interior | diagonal of "
                    f"{(s // block) ** 2}, the backward multiplies "
                    f"{share:.3f} of a run step's sub-tiles")
        products = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
        ms = _kernel_ms(bwd, (q, k, v), products)
        for name, n in products.items():
            if name not in ms:
                _say(phase, f"{name} {tag}: not measured (no device trace)")
                continue
            flops = n * 2 * b * h * (s * (s + 1) // 2) * d
            _say(phase, f"{name} {tag}: {ms[name]:.3f} ms a call, "
                        f"{flops / ms[name] / 1e9:.1f} TFLOP/s over the "
                        "causal work")

    # -- decode attention: paged bf16, paged int8 --------------------------
    # MHA at OLMoE's serving cell (16 KV heads, a query group of ONE, 48
    # slots over 16 blocks of 64), then GQA.
    shapes = [(4, 4, 2, 32, 4)] if rehearse else \
        [(48, 16, 16, 64, 16), (32, 32, 8, 64, 8)]
    for slots, hq, hkv, bs, nb in shapes:
        tag = f"{slots} slots, {hq}/{hkv} heads, block {bs}"
        s_max = bs * nb
        qd = jax.random.normal(keys[4], (slots, hq, d), jnp.float32
                               ).astype(bf16)
        positions = jax.random.randint(keys[5], (slots,), 0, s_max)
        positions = positions.at[0].set(0).at[1].set(s_max - 1)
        ck, cv = (jax.random.normal(keys[6 + i], (slots, s_max, hkv, d),
                                    jnp.float32).astype(bf16)
                  for i in range(2))
        # The same K/V as a scrambled heads-major arena + block tables.
        perm = jax.random.permutation(keys[8], slots * nb) + 1
        tables = perm.reshape(slots, nb).astype(jnp.int32)

        def to_arena(c):
            blocks = c.reshape(slots * nb, bs, hkv, d).swapaxes(1, 2)
            arena = jnp.zeros((slots * nb + 1, hkv, bs, d), c.dtype)
            return arena.at[perm].set(blocks)

        ak, av = to_arena(ck), to_arena(cv)
        kq, ks = quantize_kv(ak)
        vq, vs = quantize_kv(av)

        paged = jax.jit(
            lambda *a: paged_decode_attention(*a, use_kernel=True))
        paged8 = jax.jit(lambda q, k, v, t, p, ks, vs: paged_decode_attention(
            q, k, v, t, p, k_scale=ks, v_scale=vs, use_kernel=True))
        if not rehearse:
            for fn, args in ((paged, (qd, ak, av, tables, positions)),
                             (paged8, (qd, kq, vq, tables, positions,
                                       ks, vs))):
                assert _mosaic_calls(fn.lower(*args).compile()) == 1
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(decode_attention_reference)(qd, ck, cv, positions)
            ref8 = jax.jit(
                lambda q, k, v, t, p, ks, vs: paged_attention_reference(
                    q, k, v, t, p, k_scale=ks, v_scale=vs))(
                qd, kq, vq, tables, positions, ks, vs)
            pref = jax.jit(paged_attention_reference)(
                qd, ak, av, tables, positions)
        _check(phase, f"paged reference against the dense context's ({tag})",
               pref, ref, TOLERANCE["decode_bf16"])
        _check(phase, f"paged decode bf16 ({tag})",
               paged(qd, ak, av, tables, positions), pref,
               TOLERANCE["decode_bf16"])
        _check(phase, f"paged decode int8 ({tag})",
               paged8(qd, kq, vq, tables, positions, ks, vs), ref8,
               TOLERANCE["decode_int8"])

        # -- PR 26: live blocks only, against the walk over every entry ---
        # Every fourth slot is freed (limit 0): its row comes back zero;
        # every other row is the walk's, within a bf16 ulp (the arena's
        # own blocks a step, one softmax chain).
        freed = jnp.arange(slots) % 4 == 3
        limits = jnp.where(freed, 0, s_max).astype(jnp.int32)
        for name, (k_, v_), scales in (
                ("bf16", (ak, av), {}),
                ("int8", (kq, vq), {"k_scale": ks, "v_scale": vs})):
            got = jax.jit(lambda q, k, v, sc: paged_decode_attention(
                q, k, v, tables, positions, limits=limits, use_kernel=True,
                **sc))(qd, k_, v_, scales)
            want = jax.jit(lambda q, k, v, sc: walk_every_entry(
                q, k, v, tables, positions, **sc))(qd, k_, v_, scales)
            assert not jnp.any(jnp.where(freed[:, None, None], got, 0))
            _check(phase, f"paged decode {name}, live blocks only against "
                          f"every entry ({tag})", got,
                   jnp.where(freed[:, None, None], 0, want),
                   TOLERANCE["paged_visit"])

        # -- the engine's form: whole arena, layer index, in-place write --
        # Layer 0 holds (K, V), the last layer (V, K): a read at either
        # must be the slab call's bits, whatever lies in between.
        mid = jnp.zeros_like(ak)
        ak3, av3 = jnp.stack([ak, mid, av]), jnp.stack([av, mid, ak])
        layered = jax.jit(lambda q, k, v, t, p, li: paged_decode_attention(
            q, k, v, t, p, layer=li, use_kernel=True))
        for li, (sk, sv) in ((0, (ak, av)), (2, (av, ak))):
            _same(phase, f"paged decode at layer {li} of 3 ({tag})",
                  layered(qd, ak3, av3, tables, positions, jnp.int32(li)),
                  paged(qd, sk, sv, tables, positions))
        # A tick's write (one token a slot) and a verify window's (4
        # tokens that straddle a block boundary in every third slot;
        # every fourth slot is freed and aims at the garbage block).
        # The kernel moves the one 16-row (bf16) or 32-row (int8) tile
        # a token lands in: a start in each tile of a block, and two
        # windows across a tile boundary INSIDE a block (rows 14-17;
        # rows 30-33, which is a boundary of both tilings).
        write = jax.jit(paged_kv_write, donate_argnums=(0,))
        tiles = [bs + 16 * t + 5 for t in range(bs // 16)] \
            + [bs + 14, 2 * bs + 30]
        for width in (1, 4):
            start = jnp.where(jnp.arange(slots) % 3 == 0, bs - 2,
                              positions % (s_max - width))
            start = start.at[0].set(0).at[1].set(s_max - width)
            for i, at in zip([i for i in range(2, slots) if i % 4 != 3],
                             tiles):
                start = start.at[i].set(at)
            wpos = start[:, None] + jnp.arange(width)[None, :]
            blk = jnp.take_along_axis(tables, wpos // bs, axis=1)
            blk = jnp.where((jnp.arange(slots) % 4 == 3)[:, None], 0, blk)
            off = wpos % bs
            for name, arena, new in (
                    ("bf16", ak3, ck[:, :width]),
                    ("int8", jnp.stack([kq, kq, vq]), vq[:slots, :, :width]
                     .swapaxes(1, 2)),
                    ("scale", jnp.stack([ks, ks, vs]), vs[:slots, :, :width]
                     .swapaxes(1, 2))):
                want = arena.at[1, blk.reshape(-1), :, off.reshape(-1)].set(
                    new.reshape(-1, *new.shape[2:]))
                got = write(arena + 0, new, jnp.int32(1), blk, off)
                # The garbage block holds whichever freed row came last.
                _same(phase, f"paged_kv_write {name}, {width} token(s) a "
                             f"slot ({tag})", got[:, 1:], want[:, 1:])

    # -- grouped matmul over sorted rows (the routed block's experts) ------
    # Stacked [3, X, K, N] weights read at layer 1, against a masked loop
    # over the experts in XLA, at OLMoE's widths: decode (48 slots x top
    # 8), a one-row and an eight-row prefill of 128 tokens.
    from ray_tpu.ops.moe import grouped_matmul, grouped_swiglu

    x_, widths, row_counts = ((8, [(64, 32)], [24, 300]) if rehearse else
                              (64, [(2048, 1024), (1024, 2048)],
                               [384, 1024, 8192]))
    gmm = jax.jit(lambda a, w, g: grouped_matmul(a, w, g, jnp.int32(1),
                                                 use_kernel=True))

    # Gate and up in one call (PR 37), against the same loop's two
    # float32 products: the activation is rounded once.
    swiglu = jax.jit(lambda a, w, u, g: grouped_swiglu(
        a, w, u, g, jnp.int32(1), use_kernel=True))

    @jax.jit
    def masked_loop(a, w, g):                   # float32
        ends = jnp.cumsum(g)
        rows = jnp.arange(a.shape[0])
        out = jnp.zeros((a.shape[0], w.shape[-1]), jnp.float32)
        for e in range(w.shape[1]):
            mine = (rows >= ends[e] - g[e]) & (rows < ends[e])
            out = jnp.where(mine[:, None], jnp.dot(
                a, w[1, e], preferred_element_type=jnp.float32), out)
        return out

    for kk, nn in widths:
        w = (jax.random.normal(keys[9], (3, x_, kk, nn), jnp.float32)
             * kk ** -0.5).astype(bf16)
        for m in row_counts:
            a = jax.random.normal(keys[10], (m, kk), jnp.float32).astype(bf16)
            # Uneven groups, two of them empty, summing to m.
            cuts = jnp.sort(jax.random.randint(keys[11], (x_ - 3,), 0, m))
            g = jnp.diff(jnp.concatenate(
                [jnp.zeros(1, cuts.dtype), cuts, jnp.full(1, m, cuts.dtype)]))
            g = jnp.concatenate([g[:1], jnp.zeros(2, g.dtype), g[1:]]
                                ).astype(jnp.int32)
            if not rehearse:
                assert _mosaic_calls(gmm.lower(a, w, g).compile()) == 1
            _check(phase, f"moe_gmm [{m},{kk}] x [{x_},{kk},{nn}] bf16",
                   gmm(a, w, g), masked_loop(a, w, g).astype(bf16),
                   TOLERANCE["moe_gmm"])
            if kk > nn:                 # gate's and up's shape
                u = jnp.flip(w, axis=1)
                if not rehearse:
                    assert _mosaic_calls(
                        swiglu.lower(a, w, u, g).compile()) == 1
                want = (jax.nn.silu(masked_loop(a, w, g))
                        * masked_loop(a, u, g)).astype(bf16)
                _check(phase, f"moe_gmm silu(gate) * up [{m},{kk}] x 2 x "
                              f"[{x_},{kk},{nn}] bf16",
                       swiglu(a, w, u, g), want, TOLERANCE["moe_gmm"])
    _time_paged_cells(phase, info["kind"], rehearse)
    _latent_kernel(phase, info["kind"], rehearse)
    _gdn_kernel(phase, info["kind"], rehearse)
    _finish(phase, info)


def phase_moe(rehearse: bool) -> None:
    """The OLMoE family's forward (bf16, the dropless routed block on its
    kernel, QK-norm) against ``benchmark/reference_olmoe.py`` (float32)
    at the published widths on 4 layers; then the same comparison with
    one fault at a time in the PROGRAM's config, to show that the
    tolerances of the benchmark's configuration file catch each: the
    eighth expert dropped, the top-8 weights renormalised, QK-norm
    skipped. Tokens are the faulty forward's own greedy choices under
    teacher forcing; their gap is read off the reference's logits."""
    phase = "moe"
    info = _open_device(phase, rehearse)
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_olmoe
    from ray_tpu.models import llama

    if rehearse:
        config = llama.LlamaConfig.tiny(
            num_experts=8, num_experts_per_tok=2, qk_norm=True,
            intermediate_size=32, num_kv_heads=4)
        seq = 48
    else:
        config = llama.LlamaConfig.olmoe_1b_7b(num_layers=4, remat=False)
        seq = 160
    params = jax.jit(lambda k: llama.init_params(config, k))(
        jax.random.PRNGKey(0))
    tokens = np.random.default_rng(25).integers(1, config.vocab_size, seq)
    want = np.asarray(reference_olmoe.logits(params, tokens, config))
    want_routes = np.sort(np.asarray(
        reference_olmoe.router_choices(params, tokens, config)), -1)
    top_k = config.num_experts_per_tok
    faults = {
        "as published": config,
        "one routed expert dropped": dataclasses.replace(
            config, num_experts_per_tok=top_k - 1),
        "top-k weights renormalised": dataclasses.replace(
            config, norm_topk_prob=True),
        "QK-norm skipped": dataclasses.replace(config, qk_norm=False),
    }
    results = {}
    for name, cfg in faults.items():
        got, routes = jax.jit(lambda p, t, cfg=cfg: llama.forward(
            p, t, cfg, return_routes=True))(params, jnp.asarray(tokens)[None])
        chosen = np.asarray(jnp.argmax(got[0], axis=-1))
        under = (want.max(-1) - want[np.arange(seq), chosen]) / want.std(-1)
        routes = np.sort(np.asarray(routes), -1)
        agree = (float(np.mean(np.all(routes == want_routes, axis=-1)))
                 if routes.shape == want_routes.shape else 0.0)
        results[name] = {"worst_gap_sd": float(under.max()),
                         "router_agreement": agree}
        _say(phase, f"{name}: worst chosen-token gap {under.max():.4f} sd, "
                    f"top-{top_k} sets equal to the reference's "
                    f"{100 * agree:.2f}%")
    _finish(phase, info, faults=results)


def phase_hybrid(rehearse: bool) -> None:
    """The Granite 4.0-H family through the engine's two forwards, as
    the served path runs them (bf16, every kernel), against
    ``benchmark/reference_granite_hybrid.py`` (float32, a plain scan
    over positions) at the published widths on the stack's first two
    Mamba-2 layers and its attention layer. Two rows of teacher-forced
    tokens: ONE right-padded prefill (300 and 200 tokens in the 512
    bucket: four chunks of the scan, padding behind both rows) installs
    each row's state in its slot, then 300 decode ticks advance the
    state cache and the arena. EVERY position's logits are compared:
    the largest difference at a position (judged by its MEDIAN over
    positions: a router near-tie that bf16 flips moves one position's
    logits by a step, so the worst position reads the flips and the
    median the arithmetic), and how far the program's argmax lies under
    the reference's maximum (judged by its worst, as the benchmark
    does), both in standard deviations of the position's reference
    logits.

    Then the same comparison with one fault at a time, to show that the
    tolerances of the benchmark's configuration file catch each: the
    softmax scale ``head_dim ** -0.5`` where the family has 1/128; the
    residual multiplier dropped; the gate applied AFTER the norm; ``D``
    dropped; padding advancing the state; and the weights rounded to
    float8, the nearest precision below the bf16 the configuration
    states. The state rounded to bf16 after every tick is MEASURED
    beside them and not required to fail: over 300 ticks it moves the
    median difference by a tenth of itself (PERF.md section 6, PR 29),
    inside any tolerance that passes the program as published."""
    phase = "hybrid"
    info = _open_device(phase, rehearse)
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest, reference_granite_hybrid
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import llama, mamba2
    from ray_tpu.models.paged_kv import PagedKVCache, StateCache

    types = ("mamba", "mamba", "attention")
    if rehearse:
        config = llama.LlamaConfig.granite_4_0_h_small(
            vocab_size=256, hidden_size=64, intermediate_size=32,
            num_layers=3, layer_types=types, num_heads=4, num_kv_heads=2,
            head_dim=16, attention_multiplier=1 / 16, num_experts=8,
            num_experts_per_tok=2, shared_intermediate_size=48,
            mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
            max_seq_len=64)
        lengths, bucket, ticks, bs = (20, 13), 32, 12, 16
        tolerance = {"serve_logit_gap_sd": 1.0,
                     "smoke_median_logit_err_sd": 1.0}
    else:
        config = llama.LlamaConfig.granite_4_0_h_small(
            num_layers=3, layer_types=types, max_seq_len=1024)
        lengths, bucket, ticks, bs = (300, 200), 512, 300, 64
        tolerance = manifest.load_json(os.path.join(
            manifest.HERE, "configs", "granite-4.0-h-small-l6.json"))[
                "tolerance"]
    rows, max_blocks = len(lengths), -(-(bucket + ticks) // bs)
    params = jax.jit(lambda k: llama.init_params(config, k))(
        jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    seqs = [rng.integers(1, config.vocab_size, n + ticks) for n in lengths]
    # The reference's logits at each row's last prompt position and at
    # every tick after it: [rows, ticks + 1, V].
    want = jnp.stack([reference_granite_hybrid.logits(
        params, seq.tolist(), config)[n - 1:] for seq, n in
        zip(seqs, lengths)])
    _say(phase, f"reference: {rows} rows of {lengths} + {ticks} positions")

    prompt = np.zeros((rows, bucket), np.int32)
    for i, (seq, n) in enumerate(zip(seqs, lengths)):
        prompt[i, :n] = seq[:n]
    fed = jnp.asarray(np.stack([seq[n:] for seq, n in zip(seqs, lengths)]))
    tables = 1 + np.arange(rows * max_blocks, dtype=np.int32).reshape(
        rows, max_blocks)
    limits = jnp.full((rows,), max_blocks * bs, jnp.int32)
    last_idx = jnp.asarray(lengths, jnp.int32) - 1

    def compare(got, ref):
        """got, ref [rows, V] -> (largest difference, gap of got's
        argmax under ref's maximum), in ref's standard deviations."""
        sd = jnp.std(ref, axis=-1)
        picked = jnp.take_along_axis(
            ref, jnp.argmax(got, -1)[:, None], axis=-1)[:, 0]
        return (jnp.max(jnp.abs(got - ref), axis=-1) / sd,
                (jnp.max(ref, axis=-1) - picked) / sd)

    def run(cfg, weights, round_state=False):
        use_kernel = cb._resolve_decode_kernel(cfg, None, bs)

        @jax.jit
        def program(weights, want):
            cache = PagedKVCache.create(cfg, rows * max_blocks + 1, bs)
            state = StateCache.create(cfg, rows)
            empty = jnp.zeros((cfg.attn_layers, rows, 0, cfg.num_kv_heads,
                               cfg.head_dim), cfg.dtype)
            logits, (k, v), state = cb._prefill_forward_paged(
                weights, jnp.asarray(prompt), jnp.arange(bucket), empty,
                empty, cfg, False, last_idx, use_kernel, state,
                jnp.arange(rows))
            flat = jnp.asarray(tables[:, :bucket // bs]).reshape(-1)
            to_blocks = functools.partial(cb._ctx_to_blocks, bs=bs)
            cache = PagedKVCache(k=cache.k.at[:, flat].set(to_blocks(k)),
                                 v=cache.v.at[:, flat].set(to_blocks(v)))
            first = compare(logits[:, 0], want[:, 0])

            def tick(carry, inputs):
                caches, positions = carry
                tokens, ref = inputs
                logits, (cache, state), _ = cb._forward_paged(
                    weights, tokens[:, None], positions[:, None],
                    jnp.asarray(tables), limits, caches, cfg, use_kernel)
                if round_state:
                    # Not astype there and back: XLA may keep the excess
                    # precision of a convert pair.
                    state = state._replace(ssm=jax.lax.reduce_precision(
                        state.ssm, exponent_bits=8, mantissa_bits=7))
                return ((cache, state), positions + 1), compare(
                    logits[:, 0], ref)

            _, rest = jax.lax.scan(
                tick, ((cache, state), last_idx + 1),
                (fed.T, jnp.swapaxes(want[:, 1:], 0, 1)))
            return jax.tree.map(
                lambda a, b: jnp.concatenate([a[None], b]), first, rest)

        err, gap = (np.asarray(a) for a in program(weights, want))
        return {"prefill_err_sd": float(err[0].max()),
                "median_err_sd": float(np.median(err)),
                "p90_err_sd": float(np.percentile(err, 90)),
                "worst_err_sd": float(err.max()),
                "late_median_err_sd": float(np.median(err[-ticks // 4:])),
                "worst_gap_sd": float(gap.max()),
                "mean_gap_sd": float(gap.mean())}

    def swap(name, fn):
        """``mamba2.<name>`` replaced for one run."""
        return lambda: setattr(mamba2, name, fn)

    real_gate, real_prefill = mamba2._gate_out, mamba2.mixer_prefill

    def gate_after_norm(y, x, z, layer, c):
        y = y + layer["ssm_d"].astype(jnp.float32)[:, None] * x.astype(
            jnp.float32)
        y = y.reshape(*z.shape)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), axis=-1, keepdims=True) + c.rms_eps)
        y = (y * layer["ssm_norm"].astype(jnp.float32)
             * jax.nn.silu(z.astype(jnp.float32))).astype(c.dtype)
        return jnp.einsum("bsf,fe->bse", y, layer["ssm_out"].astype(c.dtype))

    def no_d(tree):
        return dict(tree, runs=[
            dict(run, ssm_d=jnp.zeros_like(run["ssm_d"]))
            if "ssm_d" in run else run for run in tree["runs"]])

    def float8(tree):
        return jax.tree.map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.dtype == jnp.bfloat16 else a, tree)

    measured_only = "state rounded to bf16 every tick"
    cases = [
        ("as published", config, lambda: params, {}, None),
        (measured_only, config, lambda: params, {"round_state": True},
         None),
        ("softmax scale head_dim ** -0.5", dataclasses.replace(
            config, attention_multiplier=None), lambda: params, {}, None),
        ("residual multiplier dropped", dataclasses.replace(
            config, residual_multiplier=1.0), lambda: params, {}, None),
        ("gate after the norm", config, lambda: params, {},
         swap("_gate_out", gate_after_norm)),
        ("D dropped", config, lambda: no_d(params), {}, None),
        ("padding advances the state", config, lambda: params, {},
         swap("mixer_prefill", lambda h, layer, c, n: real_prefill(
             h, layer, c, jnp.full_like(n, h.shape[1])))),
        ("weights rounded to float8_e4m3", config, lambda: float8(params),
         {}, None),
    ]
    results = {}
    for name, cfg, weights, options, patch in cases:
        if patch:
            patch()
        try:
            results[name] = run(cfg, weights(), **options)
        finally:
            mamba2._gate_out, mamba2.mixer_prefill = real_gate, real_prefill
        r = results[name]
        _say(phase, f"{name}: a position's largest logit difference, "
                    f"median {r['median_err_sd']:.4f} sd (p90 "
                    f"{r['p90_err_sd']:.4f}, worst {r['worst_err_sd']:.4f}, "
                    f"prefill {r['prefill_err_sd']:.4f}, median of the last "
                    f"quarter of the ticks {r['late_median_err_sd']:.4f}); "
                    f"chosen-token gap worst {r['worst_gap_sd']:.4f} sd, "
                    f"mean {r['mean_gap_sd']:.4f}")
    gap_tol = tolerance["serve_logit_gap_sd"]
    err_tol = tolerance["smoke_median_logit_err_sd"]
    wrong = []
    for name, r in results.items():
        caught = (r["worst_gap_sd"] > gap_tol
                  or max(r["median_err_sd"], r["late_median_err_sd"])
                  > err_tol)
        if name == measured_only or (rehearse and name != "as published"):
            continue        # tiny sizes prove nothing about the faults
        if caught != (name != "as published"):
            wrong.append(name)
    assert not wrong, f"{wrong}: {results} against {tolerance}"
    _finish(phase, info, faults=results, tolerance=tolerance)


def phase_window(rehearse: bool) -> None:
    """The cell ``serve_window_decode``'s comparison with its reference,
    and the faults it has to catch, AT THE CELL'S OWN SIZES: the
    configuration as the cell runs it (Trinity's published widths, five
    layers, experts 0-31 of 256) in the engine the served path builds
    (``ContinuousBatcher``: chunked prefill, ring and arena, every
    kernel; four slots are enough here), the cell's check prompts and
    answer length, one request after another, greedy, each keeping its
    routes; held to ``benchmark/reference_afmoe.py`` by the runner's own
    ``hold_to_reference`` under the configuration file's limits.

    First the program as published, which has to pass. Then one fault at
    a time, each of which has to FAIL one of the two limits: the
    router's scores in bf16; ``expert_bias`` dropped; the output gate
    dropped; rope on the full-attention layer; a window one block too
    long; the post-norms dropped; and the weights rounded to float8, the
    nearest precision below the bf16 the configuration states."""
    phase = "window"
    info = _open_device(phase, rehearse)
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.runners import serve_window
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import llama
    from ray_tpu.ops import moe

    cell = manifest.cell("serve_window_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    engine = dict(work["engine"], num_slots=4)
    config = serve_window.afmoe_config(cell["config"],
                                       max_seq_len=engine["max_len"])
    # Four sets of check prompts for the program as published and for
    # the fault nearest to it (the two readings the routes' limit lies
    # between); the first set alone for the other faults.
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((32,) if rehearse else (32, 33, 34, 35))]
    nearest = "router scores in bf16"

    def published():
        return jax.jit(lambda k: llama.init_params(config, k))(
            jax.random.PRNGKey(0))

    def answers(cfg, weights, sets):
        """One engine; per set of requests, (request, record) pairs."""
        eng = cb.ContinuousBatcher(cfg, params=weights, **engine)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                rid = eng.submit(req["prompt"], req["max_tokens"],
                                 keep_routes=True)
                recs.append({"tokens": eng.run_to_completion()[rid],
                             "routes": eng.take_routes(rid)})
            out.append(list(zip(reqs, recs)))
        return out

    real_route, real_finish = moe.route_sigmoid_topk, cb._layer_finish

    def bf16_route(h, w_router, k, renormalise=True, *, bias, scale=1.0):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.bfloat16), w_router.astype(jnp.bfloat16)))
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.bfloat16), k)
        weights = jnp.take_along_axis(scores, idx, -1).astype(jnp.float32)
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
        return weights * scale, idx.astype(jnp.int32)

    def no_post_norms(x, mixed, layer, c, *args, **kw):
        return real_finish(x, mixed, layer,
                           dataclasses.replace(c, sandwich_norms=False),
                           *args, **kw)

    def no_bias(tree):
        return dict(tree, runs=[
            dict(run, expert_bias=jnp.zeros_like(run["expert_bias"]))
            if "expert_bias" in run else run for run in tree["runs"]])

    def float8(tree):
        """Leaf by leaf, in place (two copies of the weights do not fit
        the chip), and op by op: inside one program the compiler drops
        a rounding whose float8 result it never has to store."""
        def rounded(a):
            if a.dtype != jnp.bfloat16:
                return a
            out = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            a.delete()
            return out
        return jax.tree.map(rounded, tree)

    replace = dataclasses.replace
    cases = [
        ("as published", config, lambda p: p, None),
        (nearest, config, lambda p: p,
         lambda: setattr(moe, "route_sigmoid_topk", bf16_route)),
        ("expert_bias dropped", config, no_bias, None),
        ("output gate dropped", replace(config, attn_gate=False),
         lambda p: p, None),
        ("rope on the full-attention layer",
         replace(config, rope_full_attention=True), lambda p: p, None),
        ("window one block too long",
         replace(config, sliding_window=config.sliding_window
                 + engine["block_size"]), lambda p: p, None),
        ("post-norms dropped", config, lambda p: p,
         lambda: setattr(cb, "_layer_finish", no_post_norms)),
        # Last: it eats the published weights.
        ("weights rounded to float8_e4m3", config, float8, None),
    ]
    if rehearse:
        cases = cases[:1]       # tiny sizes prove nothing about the faults
    params = published()
    answered = {}
    for name, cfg, weights, patch in cases:
        if patch:
            patch()
        try:
            answered[name] = answers(
                cfg, weights(params),
                sets if name in ("as published", nearest) else sets[:1])
        finally:
            moe.route_sigmoid_topk, cb._layer_finish = real_route, real_finish
        gc.collect()            # the engine's caches and relaid weights
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    if len(cases) > 1:
        del params
        params = published()
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_window.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
    wrong = [name for name, rs in results.items()
             if any(r["ok"] != (name == "as published") for r in rs)]
    assert not wrong, f"{wrong}: {results} against {tolerance}"
    _finish(phase, info, faults=results, tolerance=tolerance)


def phase_mla(rehearse: bool) -> None:
    """The cell ``serve_mla_decode``'s comparison with its reference, and
    the faults it has to catch, AT THE CELL'S OWN SIZES: the
    configuration as the cell runs it (Kimi K2's published widths, five
    layers, experts 0-11 of 384) in the engine the served path builds
    (``ContinuousBatcher``: chunked prefill in expanded form, the latent
    cache, absorbed ticks, every kernel; four slots are enough here), the
    cell's check prompts and answer length, one request after another,
    greedy, each keeping its routes; held to
    ``benchmark/reference_kimi_k2.py`` by the runner's own
    ``hold_to_reference`` under the configuration file's limits.

    First the program as published, which has to pass. Then one fault at
    a time, each of which has to FAIL one of the two limits: the cache
    rows kept in 8 bits; the rope term of the score dropped; and the
    weights rounded to float8, the nearest precision below the bf16 the
    configuration states. The tick's scores in bf16 are read as well and
    held to nothing: no limit that passes the published program sees
    them at these sizes."""
    phase = "mla"
    info = _open_device(phase, rehearse)
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.runners import serve_mla
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import llama, mla

    cell = manifest.cell("serve_mla_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    engine = dict(work["engine"], num_slots=4)
    config = serve_mla.kimi_config(cell["config"],
                                   max_seq_len=engine["max_len"])
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((36,) if rehearse else (36, 37))]

    def published():
        return jax.jit(lambda k: llama.init_params(config, k))(
            jax.random.PRNGKey(0))

    def answers(weights, sets):
        eng = cb.ContinuousBatcher(config, params=weights, **engine)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                rid = eng.submit(req["prompt"], req["max_tokens"],
                                 keep_routes=True)
                recs.append({"tokens": eng.run_to_completion()[rid],
                             "routes": eng.take_routes(rid)})
            out.append(list(zip(reqs, recs)))
        return out

    real = {name: getattr(mla, name) for name in
            ("_latents", "_queries", "latent_decode_attention")}

    def rows_in_8_bits(*args):
        row = real["_latents"](*args)
        return row.astype(jnp.float8_e4m3fn).astype(row.dtype)

    def no_rope_term(*args):
        q_nope, q_rope = real["_queries"](*args)
        return q_nope, jnp.zeros_like(q_rope)

    def bf16_scores(q, arena, tables, positions, scale, *, rank, layer=None,
                    **_):
        slab = arena if layer is None else arena[layer]
        b, nb = tables.shape
        rows = slab[tables][:, :, 0].reshape(b, -1, slab.shape[-1])
        s = jnp.einsum("bhw,bkw->bhk", q, rows) * jnp.asarray(scale, q.dtype)
        seen = positions[:, None] >= jnp.arange(rows.shape[1])[None, :]
        p = jax.nn.softmax(jnp.where(seen[:, None], s.astype(jnp.float32),
                                     -1e30), axis=-1)
        return jnp.einsum("bhk,bkc->bhc", p.astype(q.dtype),
                          rows[..., :rank]).astype(q.dtype)

    def float8(tree):
        """Leaf by leaf, in place, and op by op (two copies of the
        weights do not fit the chip)."""
        def rounded(a):
            if a.dtype != jnp.bfloat16:
                return a
            out = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            a.delete()
            return out
        return jax.tree.map(rounded, tree)

    cases = [
        ("as published", lambda p: p, {}),
        ("cache rows in 8 bits", lambda p: p, {"_latents": rows_in_8_bits}),
        ("tick scores in bf16", lambda p: p,
         {"latent_decode_attention": bf16_scores}),
        ("rope term dropped", lambda p: p, {"_queries": no_rope_term}),
        # Last: it eats the published weights.
        ("weights rounded to float8_e4m3", float8, {}),
    ]
    if rehearse:
        cases = cases[:1]       # tiny sizes prove nothing about the faults
    params = published()
    answered = {}
    for name, weights, patch in cases:
        for attr, fn in patch.items():
            setattr(mla, attr, fn)
        try:
            answered[name] = answers(
                weights(params), sets if name == "as published" else sets[:1])
        finally:
            for attr, fn in real.items():
                setattr(mla, attr, fn)
        gc.collect()
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    if len(cases) > 1:
        del params
        params = published()
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_mla.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
    _finish(phase, info, faults=results, tolerance=tolerance)
    # bf16 scores in the tick are READ, not held: they moved neither
    # number out of the published program's own range (PERF.md, PR 36).
    wrong = [name for name, rs in results.items()
             if name != "tick scores in bf16"
             and any(r["ok"] != (name == "as published") for r in rs)]
    assert not wrong, f"{wrong}: {results} against {tolerance}"


def phase_linear(rehearse: bool) -> None:
    """The cell ``serve_linear_decode``'s comparison with its reference,
    and the faults it has to catch, AT THE CELL'S OWN SIZES: the
    configuration as the cell runs it (Qwen3-Next's published widths,
    eight layers, experts 0-63 of 512) in the engine the served path
    builds (``ContinuousBatcher``: chunked prefill that carries each
    linear layer's state and conv tail, the state cache, ``gdn_step``
    ticks, every kernel; four slots are enough here), the cell's check
    prompts and answer length, one request after another, greedy, each
    keeping its routes; held to ``benchmark/reference_qwen3_next.py`` by
    the runner's own ``hold_to_reference`` under the configuration file's
    limits.

    First the prefill's scan kernel against the ``jax.numpy`` scan
    (:func:`_gdn_scan_kernel`). Then the program as published, which has
    to pass. Then one fault at a time, each of which has to FAIL one of
    the two limits (ISSUE 38, Tentpole 5b). A bfloat16 recurrent state is
    read as well and held to nothing."""
    phase = "linear"
    info = _open_device(phase, rehearse)
    _gdn_scan_kernel(phase, rehearse)
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.runners import serve_linear
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import gated_delta, llama
    from ray_tpu.ops.norms import rms_norm

    cell = manifest.cell("serve_linear_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    engine = dict(work["engine"], num_slots=4)
    config = serve_linear.qwen3_next_config(cell["config"],
                                            max_seq_len=engine["max_len"])
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((38,) if rehearse else (38, 39))]

    def published():
        return jax.jit(lambda k: llama.init_params(config, k))(
            jax.random.PRNGKey(0))

    def answers(weights, sets, config):
        eng = cb.ContinuousBatcher(config, params=weights, **engine)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                rid = eng.submit(req["prompt"], req["max_tokens"],
                                 keep_routes=True)
                recs.append({"tokens": eng.run_to_completion()[rid],
                             "routes": eng.take_routes(rid)})
            out.append(list(zip(reqs, recs)))
        return out

    real = {(mod, name): getattr(mod, name) for mod, name in (
        (gated_delta, "_gates"), (gated_delta, "_l2norm"),
        (gated_delta, "mixer_prefill"), (gated_delta, "mixer_step"),
        (llama, "norm"), (llama, "attn_gate"))}
    gates, prefill, tick = (real[gated_delta, "_gates"],
                            real[gated_delta, "mixer_prefill"],
                            real[gated_delta, "mixer_step"])

    def beta_one(b, a, layer):
        g, beta = gates(b, a, layer)
        return g, jnp.ones_like(beta)

    def no_decay(b, a, layer):
        g, beta = gates(b, a, layer)
        return jnp.zeros_like(g), beta

    def carrying(keep_state: bool, keep_tail: bool):
        def mixer_prefill(h, layer, c, lengths, carried=None):
            if carried is not None:
                state, tail = carried
                carried = (state if keep_state else jnp.zeros_like(state),
                           tail if keep_tail else jnp.zeros_like(tail))
            return prefill(h, layer, c, lengths, carried)
        return mixer_prefill

    def padding_advances(h, layer, c, lengths, carried=None):
        return prefill(h, layer, c, jnp.full_like(lengths, h.shape[1]),
                       carried)

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    def bf16_state_prefill(h, layer, c, lengths, carried=None):
        out, state, tail = prefill(h, layer, c, lengths, carried)
        return out, rounded(state), tail

    def bf16_state_tick(h, layer, c, state_all, conv_all, index,
                        use_kernel=None):
        out, state_all, conv_all = tick(h, layer, c, state_all, conv_all,
                                        index, use_kernel)
        return out, rounded(state_all), conv_all

    def float8(tree):
        """Leaf by leaf, in place, and op by op."""
        def low(a):
            if a.dtype != jnp.bfloat16:
                return a
            out = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            a.delete()
            return out
        return jax.tree.map(low, tree)

    same = lambda p: p
    replace = dataclasses.replace
    G, L = gated_delta, llama
    cases = [
        ("as published", same, {}, config),
        ("beta fixed at 1", same, {(G, "_gates"): beta_one}, config),
        ("decay dropped (g = 0)", same, {(G, "_gates"): no_decay}, config),
        ("q and k not L2-normalised", same,
         {(G, "_l2norm"): lambda x: x.astype(jnp.float32)}, config),
        ("(1 + w) read as w", same,
         {(L, "norm"): lambda x, w, c: rms_norm(x, w, c.rms_eps)}, config),
        ("rope over all 256 dims", same, {},
         replace(config, partial_rotary_factor=1.0)),
        ("attention gate dropped", same,
         {(L, "attn_gate"): lambda h, layer, c: None}, config),
        ("shared expert ungated", same, {},
         replace(config, shared_expert_gate=False)),
        ("state not carried into chunk 1", same,
         {(G, "mixer_prefill"): carrying(False, True)}, config),
        ("conv tail not carried into chunk 1", same,
         {(G, "mixer_prefill"): carrying(True, False)}, config),
        ("padding advancing the state", same,
         {(G, "mixer_prefill"): padding_advances}, config),
        ("recurrent state in bf16", same,
         {(G, "mixer_prefill"): bf16_state_prefill,
          (G, "mixer_step"): bf16_state_tick}, config),
        # Last: it eats the published weights.
        ("weights rounded to float8_e4m3", float8, {}, config),
    ]
    read_only = {"recurrent state in bf16"}
    if rehearse:
        cases = cases[:1]       # tiny sizes prove nothing about the faults
    params = published()
    answered = {}
    for name, weights, patch, case_config in cases:
        for (mod, attr), fn in patch.items():
            setattr(mod, attr, fn)
        try:
            answered[name] = answers(
                weights(params), sets if name == "as published" else sets[:1],
                case_config)
        finally:
            for (mod, attr), fn in real.items():
                setattr(mod, attr, fn)
        gc.collect()
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    if len(cases) > 1:
        del params
        params = published()
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_linear.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
    _finish(phase, info, faults=results, tolerance=tolerance)
    wrong = [name for name, rs in results.items() if name not in read_only
             and any(r["ok"] != (name == "as published") for r in rs)]
    assert not wrong, f"{wrong}: {results} against {tolerance}"


def phase_eva(rehearse: bool) -> None:
    """The cell ``serve_eva_decode``'s comparison with its reference,
    and the faults it has to catch, AT THE CELL'S OWN SIZES: the
    configuration as the cell runs it (EvaByte's published widths, eight
    layers) in the engine the served path builds (``ContinuousBatcher``:
    a window a prefill chunk, summaries landed by the prefill, windows
    closed inside the tick, blocks retired by the host; four slots are
    enough here), the cell's check prompts and answer length, one request
    after another, greedy; held to ``benchmark/reference_evabyte.py`` by
    the runner's own ``hold_to_reference`` under the configuration file's
    limit.

    First the program as published, which has to pass. Then one fault at
    a time, each of which has to FAIL the limit (ISSUE 43, Tentpole 4).
    Five are put into the PROGRAM. Three change what a query sees, which
    the program's kernels decide by one causal mask over a compressed
    position, so they are put into the REFERENCE instead and the sound
    program's bytes are held to it: the same distance read from the
    other side."""
    phase = "eva"
    info = _open_device(phase, rehearse)
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest, reference_evabyte
    from benchmark.runners import serve_eva
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import eva, llama

    cell = manifest.cell("serve_eva_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    config = serve_eva.eva_config(cell["config"],
                                  max_seq_len=work["engine"]["max_len"])
    bs, window = work["engine"]["block_size"], config.eva_window
    engine = dict(work["engine"], num_slots=4, num_blocks=4 * eva.blocks_peak(
        work["engine"]["max_len"], config, bs) + 1)
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((43,) if rehearse else (43, 44))]

    def published():
        return jax.jit(lambda k: llama.init_params(config, k))(
            jax.random.PRNGKey(0))

    def answers(weights, sets):
        eng = cb.ContinuousBatcher(config, params=weights, **engine)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                rid = eng.submit(req["prompt"], req["max_tokens"])
                recs.append({"tokens": eng.run_to_completion()[rid]})
            out.append(list(zip(reqs, recs)))
        return out

    Batcher = cb.ContinuousBatcher
    real = {(mod, name): getattr(mod, name) for mod, name in (
        (eva, "summarise"), (eva, "close_windows"),
        (cb, "paged_chunk_attention"), (cb, "paged_decode_attention"),
        (cb, "_rope_tables"), (Batcher, "_eva_step_blocks"),
        (reference_evabyte, "seen"), (reference_evabyte, "_rope"),
        (reference_evabyte, "summarise"))}
    summarise = real[eva, "summarise"]

    # The faults in the program.
    def no_mu(k, v, phi, mu, chunk):
        return summarise(k, v, phi, jnp.zeros_like(mu), chunk)

    def uniform(k, v, phi, mu, chunk):
        return summarise(k, v, jnp.zeros_like(phi), mu, chunk)

    seen_at = {}            # the true positions of the program being traced

    def rope_tables(c, length, positions):
        seen_at["p"] = positions
        return real[cb, "_rope_tables"](c, length, positions)

    def chunk_sees_no_summary(q, k, v, ak, av, layer, tables, first,
                              chunk_pos, scale, **kw):
        return real[cb, "paged_chunk_attention"](
            q, k, v, ak, av, layer, tables[:, :0], 0, chunk_pos, scale, **kw)

    def tick_sees_no_summary(q, ck, cv, tables, positions, scale, *,
                             layer=None, visits=None, **kw):
        # The table turned so that the open window's first block leads,
        # the position counted from there: the summaries fall behind it.
        first = seen_at["p"][:, 0] // window * (eva.summaries(config) // bs)
        turned = jnp.take_along_axis(
            tables, (jnp.arange(tables.shape[1])[None] + first[:, None])
            % tables.shape[1], axis=1)
        return real[cb, "paged_decode_attention"](
            q, ck, cv, turned, positions - first * bs, scale, layer=layer,
            **kw)

    def not_written(k, v, *rest):
        return k, v

    def retired_early(self, members, before):
        real[Batcher, "_eva_step_blocks"](self, members, before)
        if not before:
            return
        ahead = self._ahead()
        for slot, rid in members:       # the tick about to go would fill
            written = self._slots[slot]["pos"] + ahead.get((slot, rid), 0) + 1
            if written % window == 0:
                blocks = self._slot_blocks[slot]
                keep = eva.blocks_held(written, config, bs)
                self.allocator.free(blocks[keep:])
                del blocks[keep:]
                self._tables_stale = True

    def float8(tree):
        """Leaf by leaf, in place, and op by op."""
        def low(a):
            if a.dtype != jnp.bfloat16:
                return a
            out = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            a.delete()
            return out
        return jax.tree.map(low, tree)

    # The faults in the reference (what a query sees).
    R = reference_evabyte

    def own_chunks_too(s, window, chunk):
        i = jnp.arange(s)
        ends = jnp.arange(s // chunk) * chunk + chunk - 1
        extra = ((ends[None, :] // window == (i // window)[:, None])
                 & (ends[None, :] <= i[:, None]))
        return real[R, "seen"](s, window, chunk) | jnp.concatenate(
            [jnp.zeros((s, s), bool), extra], axis=1)

    def sliding(s, window, chunk):
        i = jnp.arange(s)
        ends = jnp.arange(s // chunk) * chunk + chunk - 1
        raw = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
        return jnp.concatenate(
            [raw, ends[None, :] <= i[:, None] - window], axis=1)

    unrotated = []

    def remember(x, theta):
        unrotated.append(x)
        return real[R, "_rope"](x, theta)

    def pool_unrotated(k, v, phi, mu, chunk):
        return real[R, "summarise"](unrotated[-1], v, phi, mu, chunk)

    same = lambda p: p
    E = eva
    program_cases = [
        ("as published", same, {}),
        ("mu dropped", same, {(E, "summarise"): no_mu}),
        ("uniform pooling (phi = 0)", same, {(E, "summarise"): uniform}),
        ("summaries dropped (window only)", same,
         {(cb, "_rope_tables"): rope_tables,
          (cb, "paged_chunk_attention"): chunk_sees_no_summary,
          (cb, "paged_decode_attention"): tick_sees_no_summary}),
        ("a tick-closed window's summaries not written", same,
         {(E, "close_windows"): not_written}),
        ("a window's blocks retired one tick early", same,
         {(Batcher, "_eva_step_blocks"): retired_early}),
        # Last: it eats the published weights.
        ("weights rounded to float8_e4m3", float8, {}),
    ]
    reference_cases = [
        ("reference: own window's whole chunks also seen as summaries",
         {(R, "seen"): own_chunks_too}),
        ("reference: a sliding window in place of the block",
         {(R, "seen"): sliding}),
        ("reference: summaries pooled before the rotation",
         {(R, "_rope"): remember, (R, "summarise"): pool_unrotated}),
    ]
    if rehearse:            # tiny sizes prove nothing about the faults
        program_cases, reference_cases = program_cases[:1], []

    def patched(patch):
        for (mod, attr), fn in patch.items():
            setattr(mod, attr, fn)

    def restore():
        for (mod, attr), fn in real.items():
            setattr(mod, attr, fn)
        R._gaps.clear_cache()

    params = published()
    answered = {}
    for name, weights, patch in program_cases:
        patched(patch)
        try:
            answered[name] = answers(
                weights(params), sets if name == "as published" else sets[:1])
        finally:
            restore()
        gc.collect()
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    _print_memory(phase)
    if len(program_cases) > 1:      # float8 ate the weights
        del params
        params = published()
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_eva.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
    for name, patch in reference_cases:
        _say(phase, name)
        patched(patch)
        R._gaps.clear_cache()
        try:
            results[name] = [serve_eva.hold_to_reference(
                params, config, answered["as published"][0], tolerance)]
        finally:
            restore()
    _finish(phase, info, faults=results, tolerance=tolerance)
    # Read and held to nothing: at seeded weights these two move no
    # chosen byte's rank (PERF.md section 7 keeps them, with readings).
    read_only = {"uniform pooling (phi = 0)", reference_cases[0][0]
                 } if reference_cases else set()
    wrong = [name for name, rs in results.items() if name not in read_only
             and any(r["ok"] != (name == "as published") for r in rs)]
    assert not wrong, f"{wrong}: {results} against {tolerance}"


def phase_cca(rehearse: bool) -> None:
    """The cell ``serve_cca_decode``'s comparison with its reference,
    and the faults it has to catch, AT THE CELL'S OWN SIZES: the
    configuration as the cell runs it (ZAYA1-8B's published widths, ten
    layers) in the engine the served path builds (``ContinuousBatcher``:
    chunks of 1024 that carry each layer's convolution tail, ticks
    through the arena and the tail cache; four slots are enough here),
    the cell's check prompts and answer length, one request after
    another, greedy, asking for its routes; held to
    ``benchmark/reference_zaya.py`` (whole sequences, a block of queries
    at a time, so that it fits beside the weights) by the runner's own
    ``hold_to_reference`` under the configuration file's two limits.

    First the program as published, on two sets of prompts, which has to
    pass. Then one fault at a time (ISSUE 46 D), which has to FAIL a
    limit: seven in the PROGRAM; one, scores rounded to bfloat16
    before the softmax, which the program's kernels decide, in the
    REFERENCE, the sound program's tokens held to it: the same distance
    read from the other side. Two of them (``a_-1 = b1``, which changes
    position 0's q and k alone, and the rounded scores) read INSIDE the
    limits at these widths and are printed, not held. (ISSUE 46 also
    lists "rope before the norm": a rotation keeps an L2 norm, so that
    is the same function, not a fault; nothing to read.)"""
    phase = "cca"
    info = _open_device(phase, rehearse)
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest, reference_zaya
    from benchmark.runners import serve_cca
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import cca
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import llama

    cell = manifest.cell("serve_cca_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    config = serve_cca.zaya_config(cell["config"],
                                   max_seq_len=work["engine"]["max_len"])
    engine = dict(work["engine"], num_slots=4, num_blocks=None)
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((46,) if rehearse else (46, 47))]

    def published():
        return jax.jit(lambda k: llama.init_params(config, k))(
            jax.random.PRNGKey(0))

    def answers(weights, sets):
        eng = cb.ContinuousBatcher(config, params=weights, **engine)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                rid = eng.submit(req["prompt"], req["max_tokens"],
                                 keep_routes=True)
                recs.append({"tokens": eng.run_to_completion()[rid],
                             "routes": eng.take_routes(rid)})
            out.append(list(zip(reqs, recs)))
        return out

    mix = cca.mix

    def no_value_shift(u, v1, v2, tail, layer, c, lengths=None):
        q, k, v, tail = mix(u, v1, v2, tail, layer, c, lengths)
        now = jnp.concatenate([v1, v2], axis=-1)
        return q, k, now.reshape(v.shape), tail

    def padded_with_b1(u, v1, v2, tail, layer, c, lengths=None):
        if tail is None:
            conv_dim, half = cca.dims(c)
            rows = u.shape[0]
            tail = jnp.concatenate([
                jnp.zeros((rows, conv_dim), u.dtype),
                jnp.broadcast_to(layer["cca_conv1_b"].astype(u.dtype),
                                 (rows, conv_dim)),
                jnp.zeros((rows, half), u.dtype)], axis=-1)
        return mix(u, v1, v2, tail, layer, c, lengths)

    def tail_not_carried(u, v1, v2, tail, layer, c, lengths=None):
        return mix(u, v1, v2, None, layer, c, lengths)

    def edited(**leaves):
        def edit(tree):
            run = dict(tree["runs"][0])
            for name, fn in leaves.items():
                if name.startswith("router_"):
                    run["router"] = dict(run["router"])
                    run["router"][name[7:]] = fn(run["router"][name[7:]])
                else:
                    run[name] = fn(run[name])
            return dict(tree, runs=[run])
        return edit

    def float8(tree):
        """Leaf by leaf, in place, and op by op."""
        def low(a):
            if a.dtype != jnp.bfloat16:
                return a
            out = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            a.delete()
            return out
        return jax.tree.map(low, tree)

    def bf16_scores(q, k, d):
        return (jnp.einsum("qhd,khd->hqk", q.astype(jnp.bfloat16),
                           k.astype(jnp.bfloat16),
                           preferred_element_type=jnp.bfloat16)
                * jnp.bfloat16(d ** -0.5)).astype(jnp.float32)

    same = lambda p: p      # noqa: E731
    program_cases = [
        ("as published", same, None),
        ("no value shift", same, no_value_shift),
        ("a_-1 = b1", same, padded_with_b1),
        ("tail not carried into a chunk or a tick", same, tail_not_carried),
        ("tau dropped", edited(cca_tau=jnp.ones_like), None),
        ("depth averaging dropped",
         edited(router_gamma=jnp.zeros_like), None),
        ("selection bias dropped", edited(router_beta=jnp.zeros_like), None),
        # Last: it eats the published weights.
        ("weights rounded to float8_e4m3", float8, None),
    ]
    reference_cases = [
        ("reference: scores rounded to bfloat16",
         {"_scores": bf16_scores}),
    ]
    if rehearse:            # tiny sizes prove nothing about the faults
        program_cases, reference_cases = program_cases[:1], []

    params = published()
    answered = {}
    for name, weights, fault in program_cases:
        if fault:
            cca.mix = fault
        try:
            answered[name] = answers(
                weights(params), sets if name == "as published" else sets[:1])
        finally:
            cca.mix = mix
        gc.collect()
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    _print_memory(phase)
    if len(program_cases) > 1:      # float8 ate the weights
        del params
        params = published()
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_cca.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
    for name, patch in reference_cases:
        _say(phase, name)
        real = {attr: getattr(reference_zaya, attr) for attr in patch}
        for attr, fn in patch.items():
            setattr(reference_zaya, attr, fn)
        try:
            results[name] = [serve_cca.hold_to_reference(
                params, config, answered["as published"][0], tolerance)]
        finally:
            for attr, fn in real.items():
                setattr(reference_zaya, attr, fn)
    _finish(phase, info, faults=results, tolerance=tolerance)
    # Read and held to nothing: these two read inside the limits (1.5 x
    # the sound program and the sound program's own reading: the
    # configuration file's ``tolerance_why``, PERF.md section 7).
    read_only = {"a_-1 = b1", "reference: scores rounded to bfloat16"}
    wrong = [name for name, rs in results.items() if name not in read_only
             and any(r["ok"] != (name == "as published") for r in rs)]
    assert not wrong, f"{wrong}: {results} against {tolerance}"


def _round_to_float8(tree):
    """Every bf16 leaf of an engine's weights rounded to ``float8_e4m3``
    and back, IN PLACE of the leaf (the chip has no room for two trees
    beside the caches): the nearest precision below the one a
    configuration states. Two programs a leaf, not one jitted round
    trip: XLA folds a narrowing conversion and its inverse away (excess
    precision)."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        if a.dtype != jnp.bfloat16:
            return a
        out = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        a.delete()
        return out

    return jax.tree.map(rounded, tree)


def phase_loop(rehearse: bool) -> None:
    """The cell ``serve_loop_decode``'s comparison with its reference,
    and the faults it has to catch, AT THE CELL'S OWN SIZES: Ouro-2.6B
    whole (48 layers at the published widths, applied four times) in the
    engine the served path builds, with the cell's slots and blocks, so
    the programs are the timed path's at the timed sizes; the cell's
    check prompts and answer length, one request after another (a
    prefill, then 31 ticks), greedy; held to
    ``benchmark/reference_ouro.py`` by the runner's own
    ``hold_to_reference`` under the configuration file's ONE limit.

    First the program as published on two sets of prompts, which has to
    pass; then every weight rounded to ``float8_e4m3`` and the six faults
    of ``tests/test_ouro.py``, one at a time, each of which should FAIL
    the limit (one that does not is printed, and PERF.md section 7 keeps
    it). Beside the chosen tokens' gap, the EXIT GATE the tick fetches
    after each step is held to the reference's at the same position:
    what 48, 96, 144 and 192 layer applications in bf16 have drifted."""
    phase = "loop"
    info = _open_device(phase, rehearse)
    import dataclasses
    import gc
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest, reference_ouro
    from benchmark.runners import serve_loop
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import llama, looped

    cell = manifest.cell("serve_loop_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    config = serve_loop.ouro_config(cell["config"],
                                    max_seq_len=work["engine"]["max_len"])
    layers, steps = config.num_layers, config.loop_steps
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((53,) if rehearse else (53, 54))]

    def answers(config, sets, weights=None):
        """Each request alone through the engine: its tokens, and the
        gates ``[T, ticks]`` its ticks fetched."""
        seen = []

        class Spied(cb.ContinuousBatcher):
            def _account_tick(self, tick_fn, tick, k):
                slot = tick["members"][0][0]
                seen.append(np.asarray(tick["row"][self.num_slots:]).view(
                    np.float32).reshape(-1, self.num_slots)[:, slot])
                super()._account_tick(tick_fn, tick, k)

        eng = Spied(config, **work["engine"])
        if weights is not None:
            eng.params = weights(eng.params)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                del seen[:]
                rid = eng.submit(req["prompt"], req["max_tokens"])
                tokens = eng.run_to_completion()[rid]
                recs.append({"tokens": tokens, "gates": np.stack(
                    seen[:len(tokens) - 1], axis=1)})
            out.append(list(zip(reqs, recs)))
        # Weights and arena fill the chip: nothing of this engine may
        # outlive it (the monitor's records keep a program's buffers).
        del eng
        gc.collect()
        for array in jax.live_arrays():
            array.delete()
        return out

    real = {(mod, name): getattr(mod, name) for mod, name in (
        (looped, "step_end"), (jax.lax, "scan"),
        (cb, "_write_then_attend"), (cb, "_layer_finish"))}
    # A program calls ``step_end`` and scans the stack once a step, in
    # order: the faults that need the step count their calls.
    ends, scans = itertools.count(1), itertools.count()

    def rows_of_step_0(arenas, li, *rest):
        return real[cb, "_write_then_attend"](arenas, li % layers, *rest)

    def reads_the_step_before(arenas, li, q, k, v, block_idx, offset, tables,
                              positions, visits, scale, use_kernel):
        # The write as it is (its own attention is dead code), then the
        # kernel alone over the row a step back: a second write-then-attend
        # whose arena is thrown away would copy 9 GB.
        _, arenas = real[cb, "_write_then_attend"](
            arenas, li, q, k, v, block_idx, offset, tables, positions,
            visits, scale, use_kernel)
        o = cb.paged_decode_attention(
            q[:, 0], arenas[0], arenas[1], tables, positions[:, 0], scale,
            layer=jnp.where(li >= layers, li - layers, li),
            visits=visits and visits[0], use_kernel=use_kernel)
        return o[:, None], arenas

    def no_norm_between(x, params, c):
        return (x if next(ends) % c.loop_steps
                else real[looped, "step_end"](x, params, c))

    def no_output_norms(x, mixed, layer, c, *rest):
        return real[cb, "_layer_finish"](
            x, mixed, layer, dataclasses.replace(c, sandwich_norms=False),
            *rest)

    def norms_of_another_layer(f, init, xs=None, **kw):
        # Four sets of weights do not fit beside the arena (21 GB): a
        # step re-indexes the four NORM vectors a layer, l -> l + step.
        if isinstance(xs, dict) and "attn_norm" in xs:
            step = next(scans) % steps
            xs = {name: (jnp.roll(a, -step, axis=0)
                         if name.endswith("norm") else a)
                  for name, a in xs.items()}
        return real[jax.lax, "scan"](f, init, xs, **kw)

    cases = [
        ("as published", config, None, {}),
        ("weights rounded to float8_e4m3", config, _round_to_float8, {}),
        ("(i) every tick reads and writes step 0's rows", config, None,
         {(cb, "_write_then_attend"): rows_of_step_0}),
        ("(ii) step t reads step t - 1's rows", config, None,
         {(cb, "_write_then_attend"): reads_the_step_before}),
        ("(iii) no final norm between steps", config, None,
         {(looped, "step_end"): no_norm_between}),
        ("(iv) three steps for four",
         dataclasses.replace(config, loop_steps=steps - 1), None, {}),
        ("(v) the two output norms dropped", config, None,
         {(cb, "_layer_finish"): no_output_norms}),
        ("(vi) another layer's norms a step", config, None,
         {(jax.lax, "scan"): norms_of_another_layer}),
    ]
    if rehearse:            # tiny sizes prove nothing about the faults
        cases = cases[:3]
    answered = {}
    for name, run_config, weights, patch in cases:
        for (mod, attr), fn in patch.items():
            setattr(mod, attr, fn)
        try:
            answered[name] = answers(
                run_config, sets if name == "as published" else sets[:1],
                weights)
        finally:
            for (mod, attr), fn in real.items():
                setattr(mod, attr, fn)
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    _print_memory(phase)
    params = jax.jit(lambda k: llama.init_params(config, k))(
        jax.random.PRNGKey(0))
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_loop.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
        # The gates: step t's over every tick of every check request.
        drift = np.zeros(steps)
        for req, rec in checked[0]:
            want = np.asarray(reference_ouro.gaps(
                params, req["prompt"], rec["tokens"], config)[1])[:, 1:]
            got = rec["gates"]
            drift[:len(got)] += np.abs(got - want[:len(got)]).mean(axis=1)
        results[name][0]["gate_gap_by_step"] = [
            float(d) / len(checked[0]) for d in drift]
        _say(phase, f"    mean |gate - reference's| after step 0..{steps - 1}"
                    f": {results[name][0]['gate_gap_by_step']}")
    _finish(phase, info, faults=results, tolerance=tolerance)
    sound = results["as published"]
    assert all(r["ok"] for r in sound), f"as published: {sound}"
    unseen = [name for name, rs in results.items()
              if name != "as published" and any(r["ok"] for r in rs)]
    if unseen:
        _say(phase, f"INSIDE the limit, so not caught: {unseen}")


def phase_jamba(rehearse: bool) -> None:
    """The cell ``serve_ssm_decode``'s comparison with its reference, and
    the faults it has to catch, AT THE CELL'S OWN SIZES: AI21-Jamba2-3B
    whole (28 layers at the published widths, 26 Mamba-1 mixers) in the
    engine the served path builds, with the cell's 256 slots and its
    blocks, so the programs are the timed path's at the timed sizes; the
    cell's check prompts (inside the bucket, on its end, on a chunk's
    last position, a second chunk of ONE token, the middle of a second
    chunk) and answer length, one request after another, greedy; held to
    ``benchmark/reference_jamba.py`` by the runner's own
    ``hold_to_reference`` under the configuration file's ONE limit.

    First the program as published on two sets of prompts, which has to
    pass; then every bf16 weight rounded to ``float8_e4m3`` and five
    faults, one at a time (patched in at ``models/mamba1.py``'s own
    functions and the two ops it calls), each of which should FAIL the
    limit (one that does not is printed, and PERF.md section 7 keeps
    it)."""
    phase = "jamba"
    info = _open_device(phase, rehearse)
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import manifest
    from benchmark.runners import serve_ssm
    from benchmark.runners.serve import _prompts
    from ray_tpu.models import continuous_batching as cb
    from ray_tpu.models import llama, mamba1
    from ray_tpu.ops import selective_scan

    cell = manifest.cell("serve_ssm_decode")
    if rehearse:
        cell = manifest.rehearsal(cell)
    work, tolerance = cell["workload"], cell["config"]["tolerance"]
    config = serve_ssm.jamba_config(cell["config"],
                                    max_seq_len=work["engine"]["max_len"])
    sets = [_prompts(np.random.default_rng(seed), config.vocab_size,
                     work["check"]["prompt_tokens"],
                     work["check"]["max_tokens"])
            for seed in ((55,) if rehearse else (55, 56))]

    def answers(sets, weights=None):
        """Each request alone through the engine: its tokens."""
        eng = cb.ContinuousBatcher(config, **work["engine"])
        if weights is not None:
            eng.params = weights(eng.params)
        out = []
        for reqs in sets:
            recs = []
            for req in reqs:
                rid = eng.submit(req["prompt"], req["max_tokens"])
                recs.append({"tokens": eng.run_to_completion()[rid]})
            out.append(list(zip(reqs, recs)))
        # Weights and caches fill most of the chip: nothing of this
        # engine may outlive it (the monitor keeps a program's buffers).
        del eng
        gc.collect()
        for array in jax.live_arrays():
            array.delete()
        return out

    real = {(mod, name): getattr(mod, name) for mod, name in (
        (mamba1, "_gate_out"), (mamba1, "_rms"), (mamba1, "_conv_step"),
        (selective_scan, "mamba1_scan"), (selective_scan, "mamba1_step"))}

    def no_skip(y, u, z, layer, c):
        return real[mamba1, "_gate_out"](
            y, u, z, dict(layer, m1_d=jnp.zeros_like(layer["m1_d"])), c)

    def no_dt_norm(x, weight, c):
        if weight.shape[-1] == c.mamba_dt_rank:
            return x.astype(jnp.float32)
        return real[mamba1, "_rms"](x, weight, c)

    def tail_out_of_order(tail, new, w, bias):
        out, nxt = real[mamba1, "_conv_step"](tail, new, w, bias)
        return out, jnp.roll(nxt, new.shape[-1], axis=1)

    def padding_advances(u, dt, *rest, **kw):
        # The mixer zeroes the padded positions' time steps; a softplus
        # is never exactly 0, so these are they.
        return real[selective_scan, "mamba1_scan"](
            u, jnp.where(dt == 0.0, 0.05, dt), *rest, **kw)

    def bf16_state(state_all, *rest, **kw):
        # (An astype pair would be folded away: excess precision.)
        y, new = real[selective_scan, "mamba1_step"](state_all, *rest, **kw)
        return y, jax.lax.reduce_precision(new, exponent_bits=8,
                                           mantissa_bits=7)

    cases = [
        ("as published", None, {}),
        ("weights rounded to float8_e4m3", _round_to_float8, {}),
        ("(i) no skip term D u", None, {(mamba1, "_gate_out"): no_skip}),
        ("(ii) no norm on dt", None, {(mamba1, "_rms"): no_dt_norm}),
        ("(iii) the tick's conv tail out of order", None,
         {(mamba1, "_conv_step"): tail_out_of_order}),
        ("(iv) padding advances the state", None,
         {(selective_scan, "mamba1_scan"): padding_advances}),
        ("(v) a bf16 state between ticks", None,
         {(selective_scan, "mamba1_step"): bf16_state}),
    ]
    if rehearse:            # tiny sizes prove nothing about the faults
        cases = cases[:3]
    answered = {}
    for name, weights, patch in cases:
        for (mod, attr), fn in patch.items():
            setattr(mod, attr, fn)
        try:
            answered[name] = answers(
                sets if name == "as published" else sets[:1], weights)
        finally:
            for (mod, attr), fn in real.items():
                setattr(mod, attr, fn)
        _say(phase, f"{name}: {len(answered[name])} x {len(sets[0])} check "
                    f"requests answered")
    _print_memory(phase)
    params = jax.jit(lambda k: llama.init_params(config, k))(
        jax.random.PRNGKey(0))
    results = {}
    for name, checked in answered.items():
        _say(phase, name)
        results[name] = [serve_ssm.hold_to_reference(
            params, config, checks, tolerance) for checks in checked]
    _finish(phase, info, faults=results, tolerance=tolerance)
    sound = results["as published"]
    assert all(r["ok"] for r in sound), f"as published: {sound}"
    unseen = [name for name, rs in results.items()
              if name != "as published" and any(r["ok"] for r in rs)]
    if unseen:
        _say(phase, f"INSIDE the limit, so not caught: {unseen}")


def _train_once(phase, config, mesh, batch_size, seq_len, steps, rehearse):
    """Init + ``steps`` steps on one repeated batch. Returns (losses,
    trainer, state)."""
    import jax

    from ray_tpu.models.training import (ShardedTrainer, default_optimizer,
                                         synthetic_batch)

    trainer = ShardedTrainer(
        config, mesh,
        # One warm-up step (its learning rate is 0), then full rate, so a
        # handful of steps is enough to see the loss move.
        optimizer=default_optimizer(learning_rate=3e-4, warmup_steps=1,
                                    total_steps=100))
    t0 = time.perf_counter()
    state = trainer.init_state(0)
    batch = trainer.shard_batch(
        synthetic_batch(batch_size, seq_len, config.vocab_size))
    jax.block_until_ready(state.params)
    _say(phase, f"state initialised in {time.perf_counter() - t0:.1f}s")
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = trainer.train_step(state, batch)
        losses.append(float(metrics["loss"]))
        _say(phase, f"step {i}: loss {losses[-1]:.4f} "
                    f"({time.perf_counter() - t0:.2f}s"
                    f"{', compile included' if i == 0 else ''})")
    assert trainer._step._cache_size() == 1, \
        f"{trainer._step._cache_size()} compiled signatures, expected 1"
    (compiled,) = trainer._step._compiled.values()
    n_calls = _mosaic_calls(compiled)
    _say(phase, f"compiled train step holds {n_calls} Mosaic custom "
                "call(s)")
    if not rehearse:
        assert n_calls >= 3, "flash attention gave way to mha_reference"
    return losses, trainer, state


def _print_memory(phase: str) -> None:
    import jax

    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if stats:
            _say(phase, f"device {dev.id}: bytes_in_use "
                        f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
                        f"peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                        f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f}")


def phase_train(rehearse: bool) -> None:
    phase = "train"
    info = _open_device(phase, rehearse)
    import math

    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshConfig, make_mesh

    if rehearse:
        config = llama.LlamaConfig.tiny()
        batch_size, seq_len = 4, 64
    else:
        config = _full_config(remat=True)
        batch_size, seq_len = 5, 2048
    _say(phase, f"{llama.num_params(config) / 1e6:.0f}M parameters, batch "
                f"{batch_size} x {seq_len}, one-device mesh")
    mesh = make_mesh(MeshConfig(fsdp=-1), devices=jax.devices()[:1])
    losses, _, _ = _train_once(phase, config, mesh, batch_size, seq_len,
                               steps=5, rehearse=rehearse)
    assert all(math.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    _print_memory(phase)
    _finish(phase, info, losses=losses)


# ------------------------------------------------------------ serve phases
DEPLOYMENT = "ContinuousLlamaDeployment"


def _post(port: int, path: str, payload: dict, timeout: float = 300.0):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"POST {path}: HTTP {resp.status}: "
                               f"{body[:300]!r}")
        return body
    finally:
        conn.close()


def _complete(port: int, prompt, max_tokens: int, streamed: bool):
    payload = {"prompt_token_ids": prompt, "max_tokens": max_tokens}
    if streamed:
        body = _post(port, f"/{DEPLOYMENT}/stream/generate", payload)
        items = [json.loads(line) for line in body.splitlines() if line]
        return [t for t in items if isinstance(t, int)]
    return json.loads(_post(port, f"/{DEPLOYMENT}", payload))["token_ids"]


def _wave(port: int, requests, max_tokens: int):
    """Send every (prompt, streamed) request concurrently; returns the
    token lists in order. Any failed request raises."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=len(requests)) as pool:
        futs = [pool.submit(_complete, port, p, max_tokens, s)
                for p, s in requests]
        return [f.result() for f in futs]


def _replicas(n: int, timeout_s: float = 240.0):
    """Wait until the controller routes ``n`` replicas that answer a
    health call; return their actor handles."""
    import ray_tpu

    controller = ray_tpu.get_actor("__serve_controller__")
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        reps = ray_tpu.get(controller.get_replicas.remote(DEPLOYMENT),
                           timeout=30)
        if len(reps) == n:
            try:
                for r in reps:
                    ray_tpu.get(r.health.remote(), timeout=5)
                return reps
            except Exception as e:  # noqa: BLE001 — constructing, or dead
                last = e
        time.sleep(0.5)
    raise AssertionError(
        f"{n} healthy replica(s) of {DEPLOYMENT} never came up in "
        f"{timeout_s:.0f}s; the last health call said: {last!r}")


def _engine_info(replica) -> dict:
    import ray_tpu

    return ray_tpu.get(
        replica.handle_request.remote("engine_info", (), {}), timeout=60)


def _serve_setup(phase, rehearse, num_replicas):
    """init -> serve.run -> start_http, as a user would. Returns
    (port, replicas, config, sizes)."""
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
    from ray_tpu.llm import build_continuous_llama_app
    from ray_tpu.models import llama

    if rehearse:
        # The sandbox has no chips to detect: name as many as the phase
        # needs so replica placement runs its real path on CPU devices.
        ray_tpu.init(num_tpus=num_replicas)
        config = llama.LlamaConfig.tiny()
        sizes = {"num_slots": 4, "max_len": 128, "block_size": 16,
                 "new": 8, "short": (5, 12), "long": (20, 40)}
    else:
        detected = TPUAcceleratorManager.detect_num_chips()
        _say(phase, f"chips detected without JAX: {detected}; JAX sees "
                    f"{len(jax.devices())}")
        assert detected == len(jax.devices()), \
            "chip detection disagrees with JAX"
        ray_tpu.init()
        config = _full_config()
        sizes = {"num_slots": 32, "max_len": 512, "block_size": 64,
                 "new": 32, "short": (20, 50), "long": (100, 200)}
    _say(phase, f"runtime resources: {ray_tpu.cluster_resources()}")
    t0 = time.perf_counter()
    serve.run(build_continuous_llama_app(
        config=config, num_replicas=num_replicas,
        num_slots=sizes["num_slots"], max_len=sizes["max_len"],
        block_size=sizes["block_size"]))
    port = serve.start_http(port=0)
    replicas = _replicas(num_replicas)
    _say(phase, f"{num_replicas} replica(s) up in "
                f"{time.perf_counter() - t0:.1f}s, HTTP on port {port}")
    return port, replicas, config, sizes


def _serve_teardown() -> None:
    import ray_tpu
    from ray_tpu import serve

    serve.stop_http()
    serve.shutdown()
    ray_tpu.shutdown()


def _prompts(config, lengths, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, config.vocab_size, n).tolist() for n in lengths]


def _native_planes(phase: str) -> None:
    """Which shared-memory store and channel this process got: the
    native (g++-built) ones, or the pure-Python fallback."""
    from ray_tpu._private.native_build import native_lib_path

    for name in ("shm_store", "shm_channel"):
        path = native_lib_path(name)
        _say(phase, f"{name}: " + (f"native ({path})" if path else
                                   "pure-Python fallback (g++ build failed "
                                   "or RAY_TPU_DISABLE_NATIVE set)"))


def phase_serve(rehearse: bool) -> None:
    phase = "serve"
    info = _open_device(phase, rehearse)
    from ray_tpu._private import xla_monitor

    try:
        port, (replica,), config, sz = _serve_setup(phase, rehearse, 1)
        _native_planes(phase)
        # Short prompts fit the first prefill bucket and never match the
        # prefix cache (under one block), so a resend takes the SAME
        # path and must return the same tokens. Long prompts reach two
        # more buckets; resent, they take the prefix-hit path, whose
        # agreement with the cold path is reported, not required (a
        # random model's logits are near-flat: an ulp flips the argmax).
        short = _prompts(config, [sz["short"][0]] * 2 + [sz["short"][1]] * 2,
                         seed=1)
        long_ = _prompts(config, [sz["long"][0]] * 2 + [sz["long"][1]] * 2,
                         seed=2)
        requests = ([(p, False) for p in short] + [(p, True) for p in short]
                    + [(p, i % 2 == 0) for i, p in enumerate(long_)])
        t0 = time.perf_counter()
        first = _wave(port, requests, sz["new"])
        _say(phase, f"wave 1 (warm-up): {len(requests)} concurrent requests"
                    f" in {time.perf_counter() - t0:.1f}s")
        warm = xla_monitor.program_stats("cb_tick")
        t0 = time.perf_counter()
        second = _wave(port, requests, sz["new"])
        _say(phase, f"wave 2: {len(requests)} concurrent requests in "
                    f"{time.perf_counter() - t0:.1f}s")
        for toks in first + second:
            assert len(toks) == sz["new"], (len(toks), sz["new"])
            assert all(0 <= t < config.vocab_size for t in toks)
        n = len(short)
        for i in range(n):
            assert first[i] == first[n + i] == second[i] == second[n + i], \
                f"short prompt {i}: the same prompt returned other tokens"
        agree = sum(a == b for a, b in zip(first[2 * n:], second[2 * n:]))
        _say(phase, f"same prompt, same tokens: {n} short prompts x 4 "
                    f"sends identical; prefix-hit resend identical to the "
                    f"cold send for {agree} of {len(long_)} long prompts")
        after = xla_monitor.program_stats("cb_tick")
        _say(phase, f"cb_tick: {after['compiles']} compile(s), "
                    f"{after['retraces']} retrace(s), "
                    f"{after['compile_seconds']:.1f}s compiling; cb_prefill:"
                    f" {xla_monitor.program_stats('cb_prefill')['compiles']}"
                    " bucket program(s)")
        assert after["compiles"] == warm["compiles"] == 1, (warm, after)
        eng = _engine_info(replica)
        _say(phase, f"engine: use_decode_kernel={eng['use_decode_kernel']},"
                    f" kv_dtype={eng['kv_dtype']}, device {eng['device']}")
        if not rehearse:
            assert eng["use_decode_kernel"] is True
            assert eng["device"]["platform"] == "tpu"
        _print_memory(phase)
    finally:
        _serve_teardown()
    _finish(phase, info)


def phase_multichip_train(rehearse: bool) -> None:
    phase = "multichip-train"
    info = _open_device(phase, rehearse)
    import math

    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshConfig, make_mesh

    devices = jax.devices()[:MIN_CHIPS_MULTI]
    assert len(devices) == MIN_CHIPS_MULTI, len(devices)
    if rehearse:
        config = llama.LlamaConfig.tiny()
        batch_size, seq_len = 4, 64
    else:
        config = _full_config(remat=True)
        batch_size, seq_len = 4, 2048
    mesh1 = make_mesh(MeshConfig(fsdp=-1), devices=devices[:1])
    losses1, trainer, state = _train_once(
        phase, config, mesh1, batch_size, seq_len, steps=1,
        rehearse=rehearse)
    del trainer, state
    mesh4 = make_mesh(MeshConfig(fsdp=MIN_CHIPS_MULTI), devices=devices)
    _say(phase, f"fsdp={MIN_CHIPS_MULTI} mesh device ids: "
                f"{[d.id for d in mesh4.devices.flat]}")
    losses4, trainer, state = _train_once(
        phase, config, mesh4, batch_size, seq_len, steps=3,
        rehearse=rehearse)
    # Spread, not replicated and not all on device 0: every parameter
    # and every optimizer moment has four shards on four devices, each a
    # quarter of the whole. The norm weights alone are whole on every
    # device (logical axis "norm": a few KB that every layer of a scan
    # reads, PR 45).
    leaves = jax.tree_util.tree_leaves_with_path(
        (state.params, state.opt_state))
    sharded = whole = 0
    for path, leaf in leaves:
        if leaf.ndim == 0:
            continue          # step counters are replicated scalars
        shards = leaf.addressable_shards
        assert len({s.device.id for s in shards}) == MIN_CHIPS_MULTI
        is_norm = "_norm" in jax.tree_util.keystr(path)
        parts = 1 if is_norm else MIN_CHIPS_MULTI
        assert all(s.data.size * parts == leaf.size for s in shards), (
            jax.tree_util.keystr(path), leaf.shape, shards[0].data.shape)
        sharded += not is_norm
        whole += is_norm
    _say(phase, f"{sharded} parameter/optimizer arrays, each in "
                f"{MIN_CHIPS_MULTI} quarter shards on {MIN_CHIPS_MULTI} "
                f"distinct devices; {whole} norm weights and their "
                "moments whole on each")
    rel = abs(losses4[0] - losses1[0]) / abs(losses1[0])
    _say(phase, f"first-step loss: one chip {losses1[0]:.5f}, fsdp="
                f"{MIN_CHIPS_MULTI} {losses4[0]:.5f} (relative difference "
                f"{rel:.1e}, bound {LOSS_RTOL:.0e})")
    assert rel <= LOSS_RTOL
    assert all(math.isfinite(x) for x in losses4)
    assert losses4[-1] < losses4[0], f"loss did not fall: {losses4}"
    _print_memory(phase)
    _finish(phase, info, losses=losses4)


def phase_multichip_serve(rehearse: bool) -> None:
    phase = "multichip-serve"
    info = _open_device(phase, rehearse)
    import ray_tpu

    n = MIN_CHIPS_MULTI
    try:
        port, replicas, config, sz = _serve_setup(phase, rehearse, n)
        prompts = _prompts(config, [sz["short"][0]] * n, seed=3)
        infos = []
        for i, (rep, prompt) in enumerate(zip(replicas, prompts)):
            # One request straight to each replica: all four must answer.
            out = ray_tpu.get(rep.handle_request.remote(
                "__call__", ({"prompt_token_ids": prompt,
                              "max_tokens": sz["new"]},), {}), timeout=300)
            assert len(out["token_ids"]) == sz["new"]
            eng = _engine_info(rep)
            infos.append(eng)
            mem = eng["memory_stats"] or {}
            _say(phase, f"replica {i}: device {eng['device']}, params on "
                        f"{eng['params_device_ids']}, arena on "
                        f"{eng['arena_device_ids']}, bytes_in_use "
                        f"{mem.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
                        f"use_decode_kernel={eng['use_decode_kernel']}")
            assert eng["params_device_ids"] == [eng["device"]["id"]]
            assert eng["arena_device_ids"] == [eng["device"]["id"]]
        ids = [e["device"]["id"] for e in infos]
        assert len(set(ids)) == n, f"replicas share a chip: {ids}"
        # Then through the router, as clients would.
        reqs = [(p, i % 2 == 0) for i, p in
                enumerate(_prompts(config, [sz["short"][1]] * 2 * n, seed=4))]
        outs = _wave(port, reqs, sz["new"])
        assert all(len(t) == sz["new"] for t in outs)
        _say(phase, f"{len(reqs)} routed HTTP requests answered")
        _print_memory(phase)
    finally:
        _serve_teardown()
    _finish(phase, info)


def _child(phase: str, rehearse: bool) -> int:
    """Run one phase and leave at once. A phase that is stuck says where
    (every thread's stack) shortly before the parent would kill it; the
    hard exit skips interpreter teardown, where a wedged runtime thread
    could sit on the chip until that kill."""
    import faulthandler
    import traceback

    faulthandler.dump_traceback_later(PHASE_TIMEOUT_S[phase] - 20, exit=True)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    code = 0
    try:
        CHILD_FNS[phase](rehearse)
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:  # noqa: BLE001 — report, then leave
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


CHILD_FNS = {"kernels": phase_kernels, "moe": phase_moe,
             "hybrid": phase_hybrid, "window": phase_window,
             "mla": phase_mla, "linear": phase_linear, "eva": phase_eva,
             "cca": phase_cca, "loop": phase_loop, "jamba": phase_jamba,
             "train": phase_train,
             "serve": phase_serve, "multichip-train": phase_multichip_train,
             "multichip-serve": phase_multichip_serve}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at LlamaConfig.tiny(), kernels "
                         "interpreted; proves nothing about the chip")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of: " + ", ".join(PHASES))
    ap.add_argument("--phase", choices=sorted(CHILD_FNS),
                    help=argparse.SUPPRESS)   # a child of this script
    args = ap.parse_args()
    if args.phase:
        return _child(args.phase, args.rehearse)
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s): {unknown}")
    return _parent(phases, args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
