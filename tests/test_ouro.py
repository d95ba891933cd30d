"""The Ouro family (a LOOPED stack: ``LlamaConfig.loop_steps > 1``,
``models/looped.py``) against its plain float32 reference
(``benchmark/reference_ouro.py``), on the CPU at a small size: 3 layers
applied 4 times at hidden 64, seeded weights, a float32 engine.

The engine's two forwards are driven by hand over a looped stack,
teacher-forced (:class:`Hand`: the host's half, tables and blocks, is
done here), and their logits held to the reference's one full pass; then
the engine
itself, whose tokens must be the reference's argmax; then each named
fault, switched on by patching, must FAIL that comparison; then what a
looped stack refuses, by name; and that a config with ``loop_steps ==
1`` builds what a config without the key builds.
"""

import dataclasses
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_ouro as reference  # noqa: E402
from ray_tpu._private import metrics_defs as mdefs  # noqa: E402
from ray_tpu.models import continuous_batching as cb  # noqa: E402
from ray_tpu.models import inference, llama, looped  # noqa: E402
from ray_tpu.models.continuous_batching import ContinuousBatcher  # noqa: E402
from ray_tpu.models.paged_kv import GARBAGE_BLOCK, PagedKVCache  # noqa: E402

BS, V, L, T = 8, 256, 3, 4
TICKS = 12
KERNELS = pytest.mark.parametrize("kernel", [False, True],
                                  ids=["kernels-off", "kernels-interpreted"])


def tiny(**kw):
    return llama.LlamaConfig.ouro_2_6b(**{**dict(
        vocab_size=V, hidden_size=64, intermediate_size=96, num_layers=L,
        num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=128,
        loop_steps=T, dtype=jnp.float32), **kw})


@pytest.fixture(scope="module")
def model():
    config = tiny()
    return config, llama.init_params(config, jax.random.PRNGKey(1))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, n).tolist() for n in lengths]


def _close(got, want, rel=1e-4):
    want = np.asarray(want)
    assert np.max(np.abs(np.asarray(got) - want)) < rel * float(want.std())


class Hand:
    """The engine's two forwards (``cb._prefill_chunk_paged``,
    ``cb._forward_paged``) driven by hand over a looped stack,
    teacher-forced: a prompt in chunks of ``chunk`` tokens (each attends
    the earlier ones out of the arena, as a chunked prefill or a prefix
    hit does; ``dense``: all its keys at once, as ``cb_prefill`` has it
    under ``PREFILL_DENSE_KEYS`` of them, else blockwise), then a tick a
    token."""

    def __init__(self, config, params, kernel=False, blocks=64, dense=True):
        self.c, self.kernel = config, kernel
        self.params = llama.heads_major(params)
        self.cache = PagedKVCache.create(config, blocks, BS, "bf16")
        self.free = list(range(blocks - 1, 0, -1))
        self.width = 16
        self.gates = []
        # One program a shape, as the engine's ``cb_prefill`` and
        # ``cb_tick`` are; traced by THIS hand, after any fault is patched in.
        self._prefill = jax.jit(
            lambda params, tokens, positions, cache, *tables:
            cb._prefill_chunk_paged(params, tokens, positions, cache, None,
                                    *tables, None, self.c, False, dense)[:2])
        self._tick = jax.jit(
            lambda *a: cb._forward_paged(*a, self.c, self.kernel))

    def prefill(self, prompt, chunk=None, shared=()):
        """``shared``: blocks another prompt with the same first tokens
        already filled (a prefix hit). Returns (logits at the prompt's
        end, the row's blocks)."""
        blocks = list(shared)
        at = len(blocks) * BS
        chunk = chunk or cb._bucket(len(prompt) - at, BS)
        while at < len(prompt):
            part = prompt[at:at + chunk]
            pad = cb._bucket(len(part), BS)
            new = [self.free.pop() for _ in range(-(-len(part) // BS))]
            tokens = jnp.zeros((1, pad), jnp.int32).at[0, :len(part)].set(
                jnp.asarray(part))
            tables_w = jnp.full((1, pad // BS), GARBAGE_BLOCK, jnp.int32).at[
                0, :len(new)].set(jnp.asarray(new, jnp.int32))
            logits, self.cache = self._prefill(
                self.params, tokens, at + jnp.arange(pad), self.cache,
                jnp.asarray(blocks, jnp.int32).reshape(1, len(blocks)),
                tables_w, jnp.asarray([len(part) - 1]))
            blocks += new
            at += len(part)
        return np.asarray(logits[0, 0]), blocks

    def tick(self, rows):
        """``rows``: [(token, position, blocks)]; every row's logits. A
        position that opens a block takes one."""
        tables = np.zeros((len(rows), self.width), np.int32)
        limits = np.zeros(len(rows), np.int32)
        for i, (_, p, blocks) in enumerate(rows):
            while len(blocks) * BS <= p:
                blocks.append(self.free.pop())
            tables[i] = blocks + [blocks[-1]] * (self.width - len(blocks))
            limits[i] = len(blocks) * BS
        logits, self.cache, bits = self._tick(
            self.params, jnp.asarray([[t] for t, _, _ in rows]),
            jnp.asarray([[p] for _, p, _ in rows]), jnp.asarray(tables),
            jnp.asarray(limits), self.cache)
        # What the tick's row carries behind its tokens: the gates' bits.
        self.gates.append(np.asarray(bits[:, :, 0]).view(np.float32))
        return np.asarray(logits[:, 0])


def _held_to_reference(config, params, lengths, kernel=False, chunk=None,
                       ref_config=None, ref_params=None, dense=True):
    """Prefill, then ``TICKS`` teacher-forced ticks of all rows together,
    each row's logits and gates against the reference's full pass."""
    seqs = _prompts([n + TICKS + 1 for n in lengths], seed=7)
    hand = Hand(config, params, kernel, dense=dense)
    got, rows = [], []
    for seq, n in zip(seqs, lengths):
        first, blocks = hand.prefill(seq[:n], chunk)
        got.append([first])
        rows.append(blocks)
    for t in range(TICKS):
        logits = hand.tick([(seq[n + t], n + t, blocks)
                            for seq, n, blocks in zip(seqs, lengths, rows)])
        for row, lg in enumerate(logits):
            got[row].append(lg)
    for row, (seq, n) in enumerate(zip(seqs, lengths)):
        want, gates = reference.forward(ref_params or params, seq[:-1],
                                        ref_config or config)
        assert len(got[row]) == TICKS + 1
        for g, w in zip(got[row], want[-1][n - 1:]):
            _close(g, w)
        ticked = np.stack([g[:, row] for g in hand.gates], axis=1)
        np.testing.assert_allclose(ticked, gates[:, n:], atol=2e-5)


# ----------------------------------------------------------- the model

def test_the_tree_the_arena_and_the_counts(model):
    config, params = model
    assert llama.layer_runs(config) == [("attention", 0, L, 0)]
    assert "runs" not in params and not config.layer_types
    layers = params["layers"]
    for name in ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm"):
        assert layers[name].shape == (L, 64)
        assert float(layers[name].min()) >= 0.5      # seeded, not ones
        assert float(layers[name].max()) <= 1.5 and float(layers[name].std())
    assert layers["wq"].shape == (L, 64, 4, 16)      # stacked ONCE
    assert params["exit_gate_w"].shape == (64,)
    assert float(params["exit_gate_b"]) == 0.0
    assert llama.num_params(config) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    arena = PagedKVCache.create(config, 5, BS)
    assert arena.k.shape == (T * L, 5, 4, BS, 16)
    assert arena.token_bytes() == T * L * 2 * 4 * 16 * 4
    # The published widths: 2.668B parameters, 1,572,864 B a token.
    full = llama.LlamaConfig.ouro_2_6b()
    assert round(llama.num_params(full) / 1e9, 3) == 2.668
    shape = jax.eval_shape(lambda: PagedKVCache.create(full, 89, 64))
    assert shape.k.shape == (192, 89, 16, 64, 128)
    assert 2 * shape.k.size * 2 // (89 * 64) == 1_572_864


def test_the_reference_leaves_at_the_last_step_at_the_published_threshold(
        model):
    config, params = model
    seq, = _prompts([20])
    lg, gates = reference.forward(params, seq, config)
    assert lg.shape == (T, 20, V) and gates.shape == (T, 20)
    assert (reference.exit_step(gates, 1.0) == T - 1).all()
    early = reference.exit_step(gates, 0.5)
    assert early.min() >= 0 and early.max() <= T - 1 and (early < T - 1).any()
    np.testing.assert_array_equal(reference.logits(params, seq, config),
                                  lg[-1])
    # The steps are different functions of the input: the loop is no
    # fixed point at this size.
    assert float(jnp.abs(lg[-1] - lg[0]).max()) > 0.1 * float(lg[-1].std())


# ------------------------------------- the two forwards, teacher-forced

@KERNELS
def test_prefill_then_ticks_give_the_references_logits(model, kernel,
                                                       pallas_interpret):
    """Prompts that end inside a block (13), on a block's last position
    (16) and one past it (17), 12 ticks each, three rows a tick."""
    config, params = model
    _held_to_reference(config, params, (13, 16, 17), kernel)


@pytest.mark.parametrize("dense", [True, False],
                         ids=["scores-at-once", "blockwise"])
def test_a_prompt_in_chunks_reads_each_steps_own_rows(model, dense):
    """A chunk of 8 under prompts of 21 and 24: step t of chunks 2 and 3
    reads step t's rows of the chunks before, in both prefill forms."""
    config, params = model
    _held_to_reference(config, params, (21, 24), chunk=8, dense=dense)


def test_a_shared_first_block_is_read_in_all_its_rows(model):
    """Two prompts with one first block: the second prefills its suffix
    over the first's block, and both then tick."""
    config, params = model
    (a,), (b,) = _prompts([20], seed=3), _prompts([19], seed=4)
    b = a[:BS] + b[BS:]
    hand = Hand(config, params)
    _, blocks_a = hand.prefill(a)
    first, _ = hand.prefill(b, shared=blocks_a[:1])
    _close(first, reference.logits(params, b, config)[-1])


# ----------------------------------------------------------- the engine

def _serve(config, params, prompts, max_new=TICKS, **engine):
    engine = {**dict(num_slots=4, max_len=64, block_size=BS), **engine}
    eng = ContinuousBatcher(config, params=params, **engine)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids], eng


def _argmax_of_the_reference(config, params, prompt, out):
    lg = reference.logits(params, (prompt + out)[:-1], config)
    return [int(t) for t in jnp.argmax(lg[len(prompt) - 1:], axis=-1)]


@KERNELS
def test_engine_tokens_are_the_references_argmax(model, kernel,
                                                 pallas_interpret):
    config, params = model
    prompts = _prompts((13, 16, 17, 30))
    outs, eng = _serve(config, params, prompts, use_decode_kernel=kernel)
    for prompt, out in zip(prompts, outs):
        assert out == _argmax_of_the_reference(config, params, prompt, out)
    assert eng.cache.k.shape[0] == T * L and eng.prefix_cache
    assert eng.allocator.used_count == eng.kv_block_stats()["cached"]


def test_engine_chunked_prefill_and_a_prefix_hit(model):
    """``prefill_chunk`` 16 under prompts of 30 and 41, then a prompt
    that shares the first two blocks of one of them."""
    config, params = model
    long_a, long_b = _prompts((30, 41), seed=5)
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=64,
                            block_size=BS, prefill_chunk=16)
    rids = [eng.submit(p, 6) for p in (long_a, long_b)]
    out = eng.run_to_completion()
    for prompt, rid in zip((long_a, long_b), rids):
        assert out[rid] == _argmax_of_the_reference(config, params, prompt,
                                                    out[rid])
    shares = long_b[:2 * BS] + _prompts([7], seed=6)[0]
    hits = eng.prefix_hit_tokens
    rid = eng.submit(shares, 6)
    got = eng.run_to_completion()[rid]
    assert eng.prefix_hit_tokens == hits + 2 * BS
    assert got == _argmax_of_the_reference(config, params, shares, got)


def test_the_loops_series_are_booked(model):
    config, params = model
    outs, eng = _serve(config, params, _prompts((9, 12)), max_new=5)
    rows = sum(v for _, tags, v in mdefs.CB_LOOP_ROWS.samples()
               if dict(tags) == eng._mtags)
    steps = sum(v for _, tags, v in mdefs.CB_LOOP_STEPS.samples()
                if dict(tags) == eng._mtags)
    assert rows >= 2 * 4 and steps == T * rows      # 4.0 a token
    (_, _, size), = [s for s in mdefs.CB_LOOP_KV_BYTES.samples()
                     if dict(s[1]) == eng._mtags]
    assert size == eng.cache.k.nbytes + eng.cache.v.nbytes
    # A tick's bytes: the layers' weights once a step, the rest once,
    # and a live token's K/V in all T x L rows.
    assert eng.tick_bytes_estimate(live_blocks=1) == (
        eng.param_bytes + (T - 1) * eng._layer_param_bytes
        + BS * T * L * 2 * 4 * 16 * 4)


# ------------------------------------------------------ the named faults

def _all_rows_are_step_0(monkeypatch):
    """(i) every step's ticks read and write step 0's rows."""
    attend = cb._write_then_attend
    monkeypatch.setattr(
        cb, "_write_then_attend",
        lambda arenas, li, *rest: attend(arenas, li % L, *rest))


def _reads_the_step_before(monkeypatch):
    """(ii) step t writes its rows and reads step t - 1's."""
    attend = cb._write_then_attend

    def shifted(arenas, li, *rest):
        _, arenas = attend(arenas, li, *rest)
        o, _ = attend(arenas, jnp.where(li >= L, li - L, li), *rest)
        return o, arenas

    monkeypatch.setattr(cb, "_write_then_attend", shifted)


def _no_norm_between_steps(monkeypatch):
    """(iii) the final norm between steps dropped (the last one stays:
    each program calls ``step_end`` once a step, in order)."""
    end, calls = looped.step_end, itertools.count(1)
    monkeypatch.setattr(
        looped, "step_end", lambda x, params, c:
        x if next(calls) % c.loop_steps else end(x, params, c))


def _fresh_weights_a_step(monkeypatch):
    """(vi) index ``t x L + l`` on the weights too: each program's
    step ``t`` scans layers ``t x L ..`` of a tree that has ``T x L``."""
    scan, calls = jax.lax.scan, itertools.count()

    def sliced(f, init, xs=None, **kw):
        if isinstance(xs, dict) and xs["attn_norm"].shape[0] == T * L:
            step = next(calls) % T
            xs = jax.tree.map(lambda a: a[step * L:(step + 1) * L], xs)
        return scan(f, init, xs, **kw)

    monkeypatch.setattr(jax.lax, "scan", sliced)


FAULTS = {
    "all-rows-are-step-0": (_all_rows_are_step_0, {}),
    "reads-the-step-before": (_reads_the_step_before, {}),
    "no-norm-between-steps": (_no_norm_between_steps, {}),
    "three-steps-for-four": (None, dict(loop_steps=T - 1)),
    "no-output-norms": (None, dict(sandwich_norms=False)),
    "fresh-weights-a-step": (_fresh_weights_a_step, dict(num_layers=T * L)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_named_fault_fails_the_comparison(model, monkeypatch, fault):
    """The comparison of ``test_prefill_then_ticks_...`` with one fault
    switched on, here only, by patching: it must not pass."""
    config, params = model
    patch, changed = FAULTS[fault]
    run_config, run_params = config, params
    if "num_layers" in changed:
        # A tree with a layer for every (step, layer) pair, of which the
        # reference applies the first L in every step.
        whole = dataclasses.replace(config, **changed)
        run_params = llama.init_params(whole, jax.random.PRNGKey(1))
        params = dict(run_params, layers=jax.tree.map(
            lambda a: a[:L], run_params["layers"]))
    elif changed:
        run_config = dataclasses.replace(config, **changed)
    if patch:
        patch(monkeypatch)
    with pytest.raises(AssertionError):
        _held_to_reference(run_config, run_params, (13, 17),
                           ref_config=config, ref_params=params)


# ---------------------------------------- what a looped stack cannot have

def _engine(config, **kw):
    return ContinuousBatcher(config, num_slots=2, max_len=32, block_size=BS,
                             **kw)


SERVICES = {
    "kv_dtype": lambda c: _engine(c, kv_dtype="int8"),
    "speculative": lambda c: _engine(c, spec_k=2),
    "handoff": lambda c: _engine(c, role="prefill"),
    "score_logprobs": lambda c: _engine(c).score_logprobs([1, 2], [3]),
    "llama.forward": lambda c: llama.forward(
        llama.init_params(c, jax.random.PRNGKey(0)),
        jnp.zeros((1, 8), jnp.int32), c),
    "LlamaGenerator": lambda c: inference.LlamaGenerator(c),
    "ExternalLlamaDrafter": lambda c: inference.ExternalLlamaDrafter(c),
}
NAMED = {"kv_dtype": "kv_dtype='int8'", "speculative": "speculative decoding",
         "handoff": "role='prefill'"}


def test_the_table_and_the_cases_are_one_list():
    assert set(SERVICES) == set(cb._KIND_CANNOT["looped"])
    assert not set(cb._KIND_CANNOT["looped"]) & {"prefix_cache"}


@pytest.mark.parametrize("service", list(SERVICES))
def test_a_service_that_runs_one_pass_refuses_by_name(service):
    """None runs one pass of the stack silently: each raises, naming
    itself and the loop."""
    with pytest.raises((ValueError, NotImplementedError)) as err:
        SERVICES[service](tiny())
    said = str(err.value)
    assert NAMED.get(service, service) in said
    assert "loop" in said
    if service != "llama.forward":      # its message is the forward's own
        assert cb._KIND_CANNOT["looped"][service] in said


def test_loss_fn_refuses_with_the_forward(model):
    config, params = model
    with pytest.raises(NotImplementedError, match="loop_steps"):
        llama.loss_fn(params, {"tokens": jnp.zeros((1, 8), jnp.int32)},
                      config)


# ------------------------------------------------ without a loop, nothing

def _tick_text(config, params=None, **how):
    """The lowered text of ``config``'s ``cb_tick`` at two slots."""
    eng = ContinuousBatcher(config, params=params, num_slots=2, max_len=32,
                            block_size=BS)
    row = jnp.zeros(2, jnp.int32)
    return eng._tick.lower(
        eng.params, row, row, jnp.zeros((2, eng.max_blocks), jnp.int32), row,
        eng.cache, jnp.int32(0)).as_text(**how)


def test_one_step_builds_what_a_config_without_the_key_builds():
    """``loop_steps == 1`` is every model so far: the same config object,
    tree, arena and programs: the loop in the engine's forwards runs
    once and leaves no trace in them."""
    plain = llama.LlamaConfig.tiny(dtype=jnp.float32)
    one = llama.LlamaConfig.tiny(dtype=jnp.float32, loop_steps=1)
    assert plain == one and hash(plain) == hash(one)
    key = jax.random.PRNGKey(0)
    a, b = llama.init_params(plain, key), llama.init_params(one, key)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert "exit_gate_w" not in b and "post_attn_norm" not in b["layers"]
    eng = ContinuousBatcher(one, params=b, num_slots=2, max_len=32,
                            block_size=BS)
    assert eng.cache.k.shape[0] == one.num_layers
    assert eng._account_tick.__func__ is ContinuousBatcher._account_tick
    assert _tick_text(plain, a) == _tick_text(one, a)
    assert "loop/step" not in _tick_text(one, a, debug_info=True)
    looped_text = _tick_text(tiny(), debug_info=True)
    assert "loop/step3" in looped_text and "loop/gate" in looped_text
