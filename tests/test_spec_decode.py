"""Speculative decoding in the continuous-batching tick (ISSUE 17).

Draft-and-verify decode must be a pure THROUGHPUT change: greedy outputs
bit-identical spec-on vs spec-off across the whole engine feature matrix
(paged kernel, int8 arenas, prefix cache), sampled decode
still the target distribution (rejection sampling) and still
deterministic under a fixed seed, and
k=0 — configured or adapted-to — exactly the pre-spec tick program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.inference import (ExternalLlamaDrafter, LlamaGenerator,
                                      SelfDrafter)
from ray_tpu.models.sampling import SamplingParams, filtered_probs, \
    spec_commit


@pytest.fixture(scope="module")
def setup():
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    gen = LlamaGenerator(config, max_len=128, seed=3)
    return config, gen


def _reference(gen, prompt, n):
    return list(np.asarray(
        gen.generate(np.asarray([prompt], np.int32),
                     max_new_tokens=n))[0])


def _run(config, params, reqs, **kw):
    eng = ContinuousBatcher(config, params=params, num_slots=4,
                            max_len=128, **kw)
    rids = [eng.submit(list(p), max_new_tokens=m) for p, m in reqs]
    out = eng.run_to_completion()
    return [out[r] for r in rids], eng


# ------------------------------------------------------------ bit parity

def _parity_matrix(config, gen, use_kernel):
    rng = np.random.default_rng(40)
    shared = list(rng.integers(1, 250, size=32))
    reqs = [(shared + list(rng.integers(1, 250, size=4)), 6),
            (shared + list(rng.integers(1, 250, size=2)), 5),
            (list(rng.integers(1, 250, size=7)), 7)]
    refs = [_reference(gen, p, m) for p, m in reqs]
    for kv_dtype in ("bf16", "int8"):
        # One spec-off baseline per (kernel, kv_dtype): prefix-cache
        # bit-parity is already a tier-1 guarantee of its own, so the
        # baseline doesn't vary across it.
        base, _ = _run(config, gen.params, reqs, spec_k=0,
                       use_decode_kernel=use_kernel,
                       kv_dtype=kv_dtype, block_size=16)
        for prefix in (False, True):
            spec, eng = _run(config, gen.params, reqs, spec_k=2,
                             spec_draft_layers=1,
                             spec_adaptive=False,
                             use_decode_kernel=use_kernel,
                             kv_dtype=kv_dtype,
                             prefix_cache=prefix, block_size=16)
            tag = (use_kernel, kv_dtype, prefix)
            assert spec == base, tag
            assert eng.spec_tick_count > 0, tag
            if kv_dtype == "bf16":
                assert spec == refs, tag


def test_greedy_parity_smoke(setup):
    """Fast-tier parity anchor: the two most entangled legs of the
    matrix — prefix-cache bf16, and int8 — bit-identical spec-on vs spec-off, with the bf16 leg
    also equal to the sequential generator. The full cross-product runs
    in the slow tier (`test_greedy_parity_matrix*`)."""
    config, gen = setup
    rng = np.random.default_rng(40)
    shared = list(rng.integers(1, 250, size=32))
    reqs = [(shared + list(rng.integers(1, 250, size=4)), 6),
            (list(rng.integers(1, 250, size=7)), 5)]
    refs = [_reference(gen, p, m) for p, m in reqs]
    spec_kw = dict(spec_k=2, spec_draft_layers=1, spec_adaptive=False)
    spec, eng = _run(config, gen.params, reqs,
                     prefix_cache=True, block_size=16, **spec_kw)
    assert spec == refs
    assert eng.spec_tick_count > 0
    base8, _ = _run(config, gen.params, reqs, kv_dtype="int8",
                    block_size=16)
    spec8, _ = _run(config, gen.params, reqs, kv_dtype="int8",
                    block_size=16, **spec_kw)
    assert spec8 == base8


@pytest.mark.slow
def test_greedy_parity_matrix(setup):
    """Greedy outputs are bit-identical spec-on vs spec-off across
    bf16/int8 arenas × prefix-cache on/off — and
    equal to the sequential generator wherever the arena stores full
    precision (int8 asserts spec-on == spec-off only; quantization
    perturbs logits either way)."""
    config, gen = setup
    _parity_matrix(config, gen, use_kernel=False)


@pytest.mark.slow
def test_greedy_parity_matrix_paged_kernel(setup, pallas_interpret):
    """The same spec-on/off matrix through the paged pallas kernel
    (interpret mode on CPU)."""
    config, gen = setup
    _parity_matrix(config, gen, use_kernel=True)


def test_eos_and_max_new_cut_spec_windows_exactly(setup):
    """A spec window overshooting a request's end must not leak tokens:
    max_new cuts the committed window mid-tick, and an EOS inside the
    window finishes the request right there."""
    config, gen = setup
    rng = np.random.default_rng(41)
    prompt = list(rng.integers(1, 250, size=9))
    ref = _reference(gen, prompt, 8)
    # Full-depth self-draft: every window commits k+1=3 tokens, so
    # max_new=8 ends mid-window.
    out, eng = _run(config, gen.params, [(prompt, 8)], spec_k=2,
                    spec_draft_layers=config.num_layers,
                    spec_adaptive=False)
    assert out[0] == ref
    # decoded_tokens counts decode-applied tokens; token 1 of max_new
    # comes from the prefill pass.
    assert eng.decoded_tokens == 7
    # EOS = the reference stream's 3rd token: generation stops there even
    # though the committing window ran past it.
    out, _ = _run(config, gen.params, [(prompt, 8)], spec_k=2,
                  spec_draft_layers=config.num_layers,
                  spec_adaptive=False, eos_token=ref[2])
    assert out[0] == ref[:3]


def test_external_drafter_parity_and_acceptance(setup):
    """A pluggable external drafter (own checkpoint, own dense cache)
    rides the same verify path: greedy outputs stay bit-identical, and a
    drafter that IS the target accepts well above chance."""
    config, gen = setup
    rng = np.random.default_rng(42)
    reqs = [(list(rng.integers(1, 250, size=n)), m)
            for n, m in [(6, 8), (11, 6)]]
    refs = [_reference(gen, p, m) for p, m in reqs]
    drafter = ExternalLlamaDrafter(config, params=gen.params)
    out, eng = _run(config, gen.params, reqs, spec_k=2,
                    spec_adaptive=False, drafter=drafter)
    assert out == refs
    assert eng.spec_draft_tokens > 0
    # Same params as the target: only float-path ulp differences between
    # the drafter's dense attention and the target's paged path can flip
    # an argmax, so acceptance beats the ~1/vocab chance level by far.
    assert eng.spec_accept_rate > 0.2


# ------------------------------------------------- sampled distribution

def test_spec_commit_greedy_acceptance_counts():
    """Greedy spec_commit: counts = leading exact matches + 1, committed
    row = the target's own argmax stream."""
    v = 11
    logits = np.full((2, 3, v), -10.0, np.float32)
    argmaxes = [[3, 5, 7], [2, 4, 6]]
    for b, row in enumerate(argmaxes):
        for i, t in enumerate(row):
            logits[b, i, t] = 10.0
    drafts = jnp.asarray([[3, 5], [9, 4]], jnp.int32)  # b0: all match
    committed, counts = spec_commit(drafts, None, jnp.asarray(logits),
                                    jnp.int32(0), SamplingParams())
    assert list(np.asarray(counts)) == [3, 1]
    assert np.asarray(committed).tolist() == argmaxes


def test_spec_commit_preserves_target_distribution():
    """Rejection sampling (Leviathan et al. 2023): the committed token's
    marginal equals the target's filtered distribution even when the
    proposal q is badly mismatched — measured by total variation over
    many salted steps."""
    v = 6
    sp = SamplingParams(temperature=0.9, top_p=0.8, seed=5)
    key = jax.random.PRNGKey(123)
    p_logits = jax.random.normal(key, (1, 2, v)) * 2.0
    q_logits = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, v)) * 2.0
    q = filtered_probs(q_logits, sp.temperature, sp.top_p)
    # Drafts drawn from q per step; the committed first token must still
    # be p-distributed regardless.
    n = 1500
    draft_keys = jax.random.split(jax.random.PRNGKey(7), n)
    drafts = jax.vmap(lambda k: jax.random.categorical(
        k, jnp.log(jnp.maximum(q[:, 0], 1e-38)), axis=-1)
        .astype(jnp.int32)[:, None])(draft_keys)

    def one(step, draft):
        committed, _ = spec_commit(draft, q, p_logits, step, sp)
        return committed[0, 0]

    toks = np.asarray(jax.vmap(one)(jnp.arange(n), drafts))
    target = np.asarray(
        filtered_probs(p_logits, sp.temperature, sp.top_p))[0, 0]
    empirical = np.bincount(toks, minlength=v) / n
    tv = 0.5 * np.abs(empirical - target).sum()
    assert tv < 0.06, (tv, empirical, target)
    # top_p filtering really applied: masked tokens never commit.
    assert empirical[target == 0].sum() == 0


def test_sampled_spec_deterministic_across_staggered_finishes(setup):
    """Sampled spec decode replays bit-identically: same seed twice,
    with staggered finishes that change the membership mid-stream."""
    config, gen = setup
    rng = np.random.default_rng(43)
    reqs = [(list(rng.integers(1, 250, size=6)), 4),
            (list(rng.integers(1, 250, size=10)), 9)]
    sampling = dict(temperature=0.8, top_p=0.9, seed=11)
    kw = dict(spec_k=2, spec_draft_layers=1, spec_adaptive=False,
              sampling=sampling)
    a, eng = _run(config, gen.params, reqs, **kw)
    b, _ = _run(config, gen.params, reqs, **kw)
    assert a == b, "same-seed sampled spec run not deterministic"
    assert eng.spec_tick_count > 0


# ----------------------------------------------- k=0 / adaptive ladder

def test_spec_k0_is_exactly_the_old_path(setup):
    """spec_k=0 never builds a spec program: the engine dispatches the
    plain cb_tick only."""
    config, gen = setup
    rng = np.random.default_rng(44)
    reqs = [(list(rng.integers(1, 250, size=5)), 6)]
    out, eng = _run(config, gen.params, reqs, spec_k=0)
    assert out == [_reference(gen, *reqs[0])]
    assert eng.spec_tick_count == 0 and not eng._spec_ticks
    assert eng.base_tick_count > 0
    assert eng.drafter is None
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatcher(config, params=gen.params, num_slots=2,
                          max_len=128, spec_k=-1)
    with pytest.raises(ValueError, match="vocab"):
        small = llama.LlamaConfig.tiny(dtype=jnp.float32)
        import dataclasses
        bad = dataclasses.replace(small, vocab_size=small.vocab_size * 2)
        ContinuousBatcher(config, params=gen.params, num_slots=2,
                          max_len=128, spec_k=2,
                          drafter=ExternalLlamaDrafter(bad))


def test_adaptive_k_collapses_to_plain_tick_on_bad_drafter(setup):
    """A drafter that never matches the target walks the rung ladder
    down to 0, after which the engine dispatches the EXACT pre-spec tick
    — outputs stay the reference stream throughout (greedy guarantee),
    and the compiled spec-program count stays bounded by the ladder."""
    config, gen = setup
    rng = np.random.default_rng(45)
    prompt = list(rng.integers(1, 250, size=8))
    # Random-params drafter sharing the vocab: greedy proposals are
    # noise, acceptance ~ 0.
    drafter = ExternalLlamaDrafter(config, seed=99)
    out, eng = _run(config, gen.params, [(prompt, 48)], spec_k=4,
                    spec_adaptive=True, drafter=drafter)
    assert out[0] == _reference(gen, prompt, 48)
    assert eng._spec_cur_k == 0, \
        f"controller stuck at k={eng._spec_cur_k} " \
        f"(accept={eng.spec_accept_rate:.2f})"
    assert eng.base_tick_count > 0, "plain tick never resumed"
    # Ladder-bounded compiled programs, one signature each (k+1 window
    # dims are whitelisted bucketed dims — no silent retraces).
    assert set(eng._spec_ticks) <= set(eng._spec_ladder_ks)
    for k, tick in eng._spec_ticks.items():
        assert tick._cache_size() == 1, (k, tick._cache_size())


def test_adaptive_k_probe_reenters_after_park(setup, monkeypatch):
    """Parked at k=0, the controller re-probes the bottom rung after
    RAY_TPU_SPEC_PROBE_TICKS boundaries so a recovered workload is not
    locked out of speculation forever."""
    monkeypatch.setenv("RAY_TPU_SPEC_PROBE_TICKS", "3")
    monkeypatch.setenv("RAY_TPU_SPEC_WINDOW", "8")
    config, gen = setup
    rng = np.random.default_rng(46)
    prompt = list(rng.integers(1, 250, size=5))
    eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                            max_len=128, spec_k=2,
                            spec_adaptive=True,
                            drafter=SelfDrafter(1))
    eng._spec_cur_k = 0  # as if the ladder bottomed out
    rid = eng.submit(prompt, max_new_tokens=12)
    out = eng.run_to_completion()
    assert out[rid] == _reference(gen, prompt, 12)
    assert eng.spec_tick_count > 0, "probe never re-entered speculation"


# ------------------------------------------ reservations and accounting

def test_lookahead_blocks_reserved_and_reported(setup):
    """Paged reservations carry spec_k look-ahead tokens (rejected draft
    writes must land in-reservation), and pressure_snapshot reports the
    outstanding look-ahead so routers don't see phantom free arena."""
    config, gen = setup
    eng = ContinuousBatcher(config, params=gen.params, num_slots=2,
                            max_len=64, block_size=8,
                            spec_k=4, spec_adaptive=False,
                            spec_draft_layers=1, prefix_cache=False)
    # ceil((5 + 10 + 4)/8) = 3 blocks; without look-ahead it would be 2.
    assert eng._blocks_needed(5, 10) == 3
    assert eng._lookahead_blocks(5, 10) == 1
    rid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=10)
    eng.step()
    (slot,) = eng._slots
    assert len(eng._slot_blocks[slot]) == 3
    snap = eng.pressure_snapshot()
    assert snap["kv_blocks_spec_lookahead"] == 1
    eng.run_to_completion()
    assert eng.pressure_snapshot()["kv_blocks_spec_lookahead"] == 0
    # Spec-off engines reserve WITHOUT the look-ahead (same math as the
    # seed) and report zero.
    eng0 = ContinuousBatcher(config, params=gen.params, num_slots=2,
                             max_len=64, block_size=8)
    assert eng0._blocks_needed(5, 10) == 2
    assert eng0.pressure_snapshot()["kv_blocks_spec_lookahead"] == 0
    assert rid is not None


def test_multi_token_tick_accounting(setup):
    """TPOT and decode tokens/s come from COMMITTED counts, not tick
    counts: a perfect drafter commits k+1 per tick and the books agree."""
    config, gen = setup
    rng = np.random.default_rng(47)
    prompt = list(rng.integers(1, 250, size=6))
    out, eng = _run(config, gen.params, [(prompt, 12)], spec_k=2,
                    spec_draft_layers=config.num_layers,
                    spec_adaptive=False)
    assert out[0] == _reference(gen, prompt, 12)
    # Token 1 of max_new comes from prefill; the other 11 are decode.
    assert eng.decoded_tokens == 11
    assert eng.spec_accept_rate == 1.0
    # 11 decode tokens in 3-token windows: 4 spec ticks, not 11.
    assert eng.spec_tick_count == 4
    assert eng.spec_draft_tokens == 8 and eng.spec_accepted_tokens == 8
    (bd,) = list(eng.request_breakdowns)[-1:]
    assert bd["tokens"] == 12
    assert bd["tpot_s"] is not None and bd["tpot_s"] >= 0.0
    # The spec tick prices MORE bytes than the plain tick (k draft passes
    # + the wider verify): the bytes_hint must reflect that.
    assert eng.tick_bytes_estimate(spec_k=2) > eng.tick_bytes_estimate(
        spec_k=0)
