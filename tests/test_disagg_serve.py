"""Disaggregated prefill/decode serving (ISSUE 20).

The KV-block transfer plane must be INVISIBLE to correctness: a request
split across a prefill replica and a decode replica yields the
bit-identical greedy completion the colocated engine produces, across
the whole engine feature matrix (paged kernel, int8 arenas, prefix
cache). The handoff is exactly-once under chaos — a
replica killed mid-transfer on EITHER side recovers through the request
journal without dropping, duplicating, or double-billing the transfer.
"""

import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.continuous_batching import ContinuousBatcher
from ray_tpu.models.inference import LlamaGenerator
from ray_tpu.serve import kv_transfer


@pytest.fixture(scope="module")
def setup():
    config = llama.LlamaConfig.tiny(dtype=jnp.float32)
    gen = LlamaGenerator(config, max_len=128, seed=0)
    return config, gen


def _reference(gen, prompt, n):
    return list(np.asarray(
        gen.generate(np.asarray([prompt], np.int32),
                     max_new_tokens=n))[0])


def _engine(config, params, role, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("block_size", 16)
    kw.setdefault("num_blocks", 64)
    return ContinuousBatcher(config, params=params, role=role, **kw)


def _park(pre, prompt, max_new):
    """Submit on a prefill-role engine and run until the request parks
    with handoff-ready KV; returns its rid."""
    rid = pre.submit(list(prompt), max_new_tokens=max_new)
    pre.run_to_completion()
    assert rid in pre.handoff_ready(), "request never parked for handoff"
    return rid


def _counter_value(metric, **want):
    total = 0.0
    for _, tags, v in metric.samples():
        td = dict(tags)
        if all(td.get(k) == v2 for k, v2 in want.items()):
            total += v
    return total


# ----------------------------------------------- unit: export/import parity

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_export_import_roundtrip_bit_parity(setup, kv_dtype):
    """The imported arena blocks are byte-for-byte the exported ones —
    K/V planes AND (for int8) the fp32 scale sidecars — through the
    gather → staging → scatter path."""
    config, gen = setup
    rng = np.random.default_rng(50)
    prompt = list(rng.integers(1, 250, size=33))  # 2 full blocks + tail
    pre = _engine(config, gen.params, "prefill", kv_dtype=kv_dtype)
    dst = _engine(config, gen.params, "decode", kv_dtype=kv_dtype)
    rid = _park(pre, prompt, 6)
    payload = kv_transfer.export_kv(pre, rid)
    raw = bytes(payload["staging"])
    layout = payload["layout"]
    assert payload["crc32"] and payload["nbytes"] == len(raw)
    assert payload["num_blocks"] == 3  # ceil(33/16) prompt blocks ship
    if kv_dtype == "int8":
        assert any("scale" in str(e[0]) for e in layout), \
            "int8 export must carry the scale sidecars"
    drid = kv_transfer.import_kv(dst, payload)
    slot = next(s for s, st in dst._slots.items() if st["rid"] == drid)
    blocks = dst._slot_blocks[slot][:payload["num_blocks"]]
    staged2, layout2 = dst.cache.gather_blocks(blocks)
    assert bytes(staged2) == raw
    assert [tuple(e[:3]) for e in layout2] == \
        [tuple(e[:3]) for e in layout]


def test_import_rejects_corrupt_and_mismatched_payloads(setup):
    config, gen = setup
    from ray_tpu._private import metrics_defs as mdefs

    rng = np.random.default_rng(51)
    prompt = list(rng.integers(1, 250, size=32))
    pre = _engine(config, gen.params, "prefill")
    dst = _engine(config, gen.params, "decode")
    payload = kv_transfer.export_kv(pre, _park(pre, prompt, 4))
    # Corrupted staging bytes: crc check fires and the mismatch counts.
    bad = np.array(payload["staging"], copy=True)
    bad[0] ^= 0xFF
    before = _counter_value(mdefs.SERVE_HANDOFFS, outcome="crc_mismatch")
    with pytest.raises(ValueError, match="crc"):
        kv_transfer.import_kv(dst, {**payload, "staging": bad})
    assert _counter_value(mdefs.SERVE_HANDOFFS,
                          outcome="crc_mismatch") == before + 1
    # Geometry mismatch: a different-block-size engine refuses.
    other = _engine(config, gen.params, "decode", block_size=32,
                    num_blocks=32)
    with pytest.raises(ValueError, match="geometry|block_size"):
        kv_transfer.import_kv(other, payload)
    # Version mismatch refuses before touching anything.
    with pytest.raises(ValueError, match="version"):
        kv_transfer.import_kv(dst, {**payload, "version": -1})


def test_role_knob_guards(setup):
    config, gen = setup
    with pytest.raises(ValueError):
        _engine(config, gen.params, "bogus")
    pre = _engine(config, gen.params, "prefill")
    dst = _engine(config, gen.params, "decode")
    with pytest.raises(ValueError):
        pre.reserve_import(16, 4)
    with pytest.raises(ValueError):
        pre.import_kv_payload({"version": -1})
    rng = np.random.default_rng(52)
    rid = dst.submit(list(rng.integers(1, 250, size=8)),
                     max_new_tokens=2)
    dst.run_to_completion()
    assert rid not in dst.handoff_ready()  # decode role never parks
    with pytest.raises((ValueError, KeyError)):
        dst.export_kv_payload(rid)


def test_reservation_lifecycle_and_ttl_sweep(setup, monkeypatch):
    """Pre-reservations pin arena blocks for an incoming import; unspent
    tickets expire by TTL and cancelled ones free immediately."""
    config, gen = setup
    dst = _engine(config, gen.params, "decode")
    free0 = dst.allocator.free_count
    res = dst.reserve_import(32, 8)
    assert res is not None and dst.allocator.free_count < free0
    drid_blocks = dst._import_reservations[res]["blocks"]
    assert drid_blocks
    assert dst.cancel_reservation(res)
    assert dst.allocator.free_count == free0
    # TTL sweep: a ticket whose handoff never arrives frees itself.
    res2 = dst.reserve_import(16, 4)
    assert res2 is not None
    monkeypatch.setenv("RAY_TPU_KV_RESERVE_TTL_S", "0")
    time.sleep(0.01)
    assert dst.sweep_reservations() == 1
    assert dst.allocator.free_count == free0
    assert not dst.cancel_reservation(res2)  # already swept


def test_pressure_snapshot_reports_role_fields(setup):
    config, gen = setup
    pre = _engine(config, gen.params, "prefill")
    dst = _engine(config, gen.params, "decode")
    both = _engine(config, gen.params, "both")
    for eng, role in ((pre, "prefill"), (dst, "decode"), (both, "both")):
        snap = eng.pressure_snapshot()
        assert snap["role"] == role
        assert "prefill_queue_tokens" in snap
        assert "kv_blocks_importable" in snap
    assert dst.pressure_snapshot()["kv_blocks_importable"] > 0
    res = dst.reserve_import(32, 8)
    assert res is not None
    snap = dst.pressure_snapshot()
    assert snap["kv_blocks_importable"] < dst.allocator.num_blocks
    dst.cancel_reservation(res)


def test_import_inserts_prefix_into_radix_shareable(setup):
    """The transferred prefix lands in the decode replica's radix index
    ON ARRIVAL: a follow-up request sharing the prompt matches it
    (read-only refcounted) instead of re-prefilling."""
    config, gen = setup
    rng = np.random.default_rng(53)
    shared = list(rng.integers(1, 250, size=32))
    pre = _engine(config, gen.params, "prefill", prefix_cache=True)
    dst = _engine(config, gen.params, "decode", prefix_cache=True)
    drid = kv_transfer.transfer_inproc(pre, dst, _park(pre, shared, 5))
    out = dst.run_to_completion()
    assert out[drid] == _reference(gen, shared, 5)
    # Second request with the same prompt head: the imported blocks are
    # matched from the radix index, not re-prefilled.
    twin = shared + list(rng.integers(1, 250, size=3))
    rid2 = dst.submit(twin, max_new_tokens=4)
    out2 = dst.run_to_completion()
    assert out2[rid2] == _reference(gen, twin, 4)
    assert dst.prefix_hit_rate > 0, \
        "imported prefix never matched from the radix index"


def test_journal_gate_refuses_unjournaled_manifest(setup):
    config, gen = setup
    dst = _engine(config, gen.params, "decode")
    with pytest.raises(RuntimeError, match="journal"):
        kv_transfer.receive_handoff(dst, {"channel": None})


def test_handoff_ledger_never_double_bills(setup):
    """Double-billing regression: one clean transfer journals EXACTLY
    one ledger entry, and a retried bookkeeping call for the same
    attempt is refused (idempotent), while a genuine retry attempt
    journals a distinct entry."""
    config, gen = setup
    from ray_tpu.serve.recovery import RequestJournal

    rng = np.random.default_rng(54)
    prompt = list(rng.integers(1, 250, size=32))
    pre = _engine(config, gen.params, "prefill")
    dst = _engine(config, gen.params, "decode")
    journal = RequestJournal("llm", "generate",
                             {"prompt_token_ids": prompt, "max_tokens": 4})
    drid = kv_transfer.transfer_inproc(pre, dst, _park(pre, prompt, 4),
                                       journal=journal)
    assert dst.run_to_completion()[drid] == _reference(gen, prompt, 4)
    assert len(journal.handoffs) == 1
    entry = journal.handoffs[0]
    # A duplicate note for the same attempt returns the existing entry.
    assert journal.note_handoff({"crc32": 0, "attempt": 0}) is entry
    assert len(journal.handoffs) == 1
    # A NEW attempt (death recovery replayed the prefill) bills anew.
    journal.resumes += 1
    journal.note_handoff({"crc32": 1, "attempt": 1})
    assert len(journal.handoffs) == 2
    assert [e["attempt"] for e in journal.handoffs] == [0, 1]


def test_abandoned_handoff_releases_blocks(setup):
    config, gen = setup
    rng = np.random.default_rng(55)
    # prefix_cache off: abandoned blocks free OUTRIGHT (with the radix
    # index on they would deref into the LRU "cached" state instead).
    pre = _engine(config, gen.params, "prefill", prefix_cache=False)
    free0 = pre.allocator.free_count
    rid = _park(pre, list(rng.integers(1, 250, size=32)), 4)
    assert pre.allocator.free_count < free0
    assert pre.abandon_handoff(rid)
    assert pre.allocator.free_count == free0
    assert not pre.abandon_handoff(rid)


# ------------------------------------------ colocated-vs-split bit parity

def _run_colocated(config, params, reqs, **kw):
    eng = _engine(config, params, "both", **kw)
    rids = [eng.submit(list(p), max_new_tokens=m) for p, m in reqs]
    out = eng.run_to_completion()
    return [out[r] for r in rids]


def _run_split(config, params, reqs, **kw):
    """Every request prefills on one engine, crosses the transfer plane,
    and decodes on another — the engine-level split topology."""
    pre = _engine(config, params, "prefill", **kw)
    dec = _engine(config, params, "decode", **kw)
    rids = [pre.submit(list(p), max_new_tokens=m) for p, m in reqs]
    pre_out = pre.run_to_completion()
    mapped = []
    for r in rids:
        if r in pre.handoff_ready():
            mapped.append(("d", kv_transfer.transfer_inproc(pre, dec, r)))
        else:
            mapped.append(("p", r))  # finished entirely at prefill
    dec_out = dec.run_to_completion()
    return [dec_out[r] if side == "d" else pre_out[r]
            for side, r in mapped]


def _split_parity_matrix(config, gen, use_kernel):
    rng = np.random.default_rng(60)
    shared = list(rng.integers(1, 250, size=32))
    reqs = [(shared + list(rng.integers(1, 250, size=4)), 6),
            (shared + list(rng.integers(1, 250, size=2)), 5),
            (list(rng.integers(1, 250, size=17)), 7)]
    refs = [_reference(gen, p, m) for p, m in reqs]
    for kv_dtype in ("bf16", "int8"):
        for prefix in (False, True):
            kw = dict(use_decode_kernel=use_kernel,
                      kv_dtype=kv_dtype, prefix_cache=prefix)
            colo = _run_colocated(config, gen.params, reqs, **kw)
            split = _run_split(config, gen.params, reqs, **kw)
            tag = (use_kernel, kv_dtype, prefix)
            assert split == colo, tag
            if kv_dtype == "bf16":
                assert split == refs, tag


def test_split_parity_smoke(setup):
    """Fast-tier parity anchor: the two most entangled legs — prefix
    cache bf16, and int8 — split outputs
    bit-identical to colocated (bf16 also equal to the sequential
    generator). The full cross-product runs in the slow tier."""
    config, gen = setup
    rng = np.random.default_rng(60)
    shared = list(rng.integers(1, 250, size=32))
    reqs = [(shared + list(rng.integers(1, 250, size=4)), 6),
            (list(rng.integers(1, 250, size=17)), 5)]
    refs = [_reference(gen, p, m) for p, m in reqs]
    kw = dict(prefix_cache=True)
    assert _run_split(config, gen.params, reqs, **kw) == \
        _run_colocated(config, gen.params, reqs, **kw) == refs
    kw8 = dict(kv_dtype="int8")
    assert _run_split(config, gen.params, reqs, **kw8) == \
        _run_colocated(config, gen.params, reqs, **kw8)


@pytest.mark.slow
def test_split_parity_matrix(setup):
    """Colocated-vs-split greedy outputs bit-identical across bf16/int8
    arenas × prefix-cache on/off (interpreter-path
    attention)."""
    config, gen = setup
    _split_parity_matrix(config, gen, use_kernel=False)


@pytest.mark.slow
def test_split_parity_matrix_paged_kernel(setup, pallas_interpret):
    """The same colocated-vs-split matrix through the paged pallas
    decode kernel (interpret mode on CPU)."""
    config, gen = setup
    _split_parity_matrix(config, gen, use_kernel=True)


# --------------------------------------------- serve e2e: chaos handoffs

import json  # noqa: E402
import urllib.request  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu import serve  # noqa: E402
from ray_tpu._private import chaos  # noqa: E402

PROMPT = list(range(1, 41))
PAYLOAD = {"prompt_token_ids": PROMPT, "max_tokens": 8}


@pytest.fixture(autouse=True)
def _chaos_clean():
    yield
    chaos.configure(None)


@pytest.fixture(scope="module")
def disagg_app(setup):
    """A live (2 prefill, 2 decode) role-group pair behind real HTTP
    ingress, with the classifier forced to split EVERY LLM request
    (threshold 0). Two replicas per role so a chaos-killed replica's
    retry lands on the survivor while the controller respawns."""
    from ray_tpu.llm import deploy_disagg_llama

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    os.environ["RAY_TPU_DISAGG_PREFILL_THRESHOLD"] = "0"
    ray_tpu.init(num_cpus=4)
    config, _ = setup
    deploy_disagg_llama("dllm", config=config, num_prefill=2,
                        num_decode=2, num_slots=4, max_len=128,
                        block_size=16, num_blocks=64,
                        prefix_cache=True)
    port = serve.start_http(port=0)
    yield port
    chaos.configure(None)
    os.environ.pop("RAY_TPU_DISAGG_PREFILL_THRESHOLD", None)
    serve.shutdown()
    ray_tpu.shutdown()


def _http_stream(port, payload, timeout_s=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/dllm/stream/generate",
        data=json.dumps(payload).encode(), method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        marker = r.headers.get("x-ray-tpu-resumed")
        items = [json.loads(l) for l in r.read().splitlines() if l.strip()]
    return items, marker


def _wait_group(n=2, timeout_s=90):
    """Health-probed wait for n routed replicas of BOTH role
    deployments — the clean-start point after a chaos kill."""
    controller = ray_tpu.get_actor("__serve_controller__")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            ok = True
            for name in ("dllm-prefill", "dllm-decode"):
                reps = ray_tpu.get(controller.get_replicas.remote(name),
                                   timeout=10)
                if len(reps) != n:
                    ok = False
                    break
                for r in reps:
                    ray_tpu.get(r.health.remote(), timeout=10)
            if ok:
                return
        except Exception:  # noqa: BLE001 — dead/starting: keep waiting
            pass
        time.sleep(0.2)
    raise AssertionError("role group never reached full health")


def test_split_e2e_http_parity_and_metrics(disagg_app, setup):
    """A classified request crosses prefill → channel → decode through
    real HTTP ingress and streams the bit-identical greedy completion
    the sequential generator produces; the transfer plane's metrics
    account every direction of the hop."""
    from ray_tpu._private import metrics_defs as mdefs

    _, gen = setup
    ref = _reference(gen, PROMPT, 8)
    before = {d: _counter_value(mdefs.SERVE_KV_TRANSFER_BYTES,
                                direction=d)
              for d in ("export", "channel", "import")}
    blocks0 = {d: _counter_value(mdefs.SERVE_KV_TRANSFER_BLOCKS,
                                 direction=d)
               for d in ("export", "import")}
    ok0 = _counter_value(mdefs.SERVE_HANDOFFS, outcome="ok")
    toks, marker = _http_stream(disagg_app, PAYLOAD)
    assert toks == ref
    assert marker is None  # clean greedy run: no resume marker
    assert _counter_value(mdefs.SERVE_HANDOFFS, outcome="ok") == ok0 + 1
    for d in ("export", "channel", "import"):
        assert _counter_value(mdefs.SERVE_KV_TRANSFER_BYTES,
                              direction=d) > before[d], d
    # Deltas, not totals: the counters are process-global, and earlier
    # unit tests legitimately export payloads whose imports are
    # REJECTED (crc/geometry) — those must not unbalance this hop.
    exported = _counter_value(mdefs.SERVE_KV_TRANSFER_BLOCKS,
                              direction="export") - blocks0["export"]
    imported = _counter_value(mdefs.SERVE_KV_TRANSFER_BLOCKS,
                              direction="import") - blocks0["import"]
    assert exported == imported > 0


def test_chaos_kill_export_resubmits_exactly_once(disagg_app, setup):
    """kill_transfer:stage=export is a REAL prefill replica death while
    it materializes the KV payload: nothing was journaled, so the
    submission resubmits to the surviving prefill replica and the
    stream completes bit-identically — the invisible leg."""
    from ray_tpu._private import metrics_defs as mdefs

    _, gen = setup
    _wait_group()
    ref = _reference(gen, PROMPT, 8)
    died0 = _counter_value(mdefs.SERVE_HANDOFFS, outcome="prefill_died")
    res0 = _counter_value(mdefs.SERVE_REPLICA_RESUMES, cause="resubmit")
    chaos.configure("kill_transfer:stage=export", seed=7)
    toks, marker = _http_stream(disagg_app, PAYLOAD)
    kills = [e for e in chaos.injection_log()
             if e["action"] == "kill_transfer"]
    chaos.configure(None)
    assert kills and kills[0]["coords"]["stage"] == "export"
    assert toks == ref
    assert marker is None  # resubmit is invisible: nothing had crossed
    assert _counter_value(mdefs.SERVE_HANDOFFS,
                          outcome="prefill_died") == died0 + 1
    assert _counter_value(mdefs.SERVE_REPLICA_RESUMES,
                          cause="resubmit") == res0 + 1


def test_chaos_kill_import_resumes_exactly_once_journal(disagg_app,
                                                        setup):
    """kill_transfer:stage=import kills the decode replica AFTER the
    handoff was journaled: the request replays as a fresh prefill
    (cause=resume — the first token crossed replicas), the output stays
    bit-identical, and the journal bills each attempt's handoff exactly
    once (the double-billing regression, asserted on the live ledger)."""
    from ray_tpu._private import metrics_defs as mdefs
    from ray_tpu.serve.proxy import _Router

    _, gen = setup
    _wait_group()
    ref = _reference(gen, PROMPT, 8)
    died0 = _counter_value(mdefs.SERVE_HANDOFFS, outcome="decode_died")
    res0 = _counter_value(mdefs.SERVE_REPLICA_RESUMES, cause="resume")
    chaos.configure("kill_transfer:stage=import", seed=11)
    s = _Router().stream("dllm", "generate", dict(PAYLOAD))
    s._timeout = 120.0
    toks = list(s)
    chaos.configure(None)
    assert toks == ref
    j = s.journal
    assert j.resumes == 1 and j.resumed_midstream
    # Exactly-once billing: ONE ledger entry per attempt, none repeated.
    assert [e["attempt"] for e in j.handoffs] == [0, 1]
    assert _counter_value(mdefs.SERVE_HANDOFFS,
                          outcome="decode_died") == died0 + 1
    assert _counter_value(mdefs.SERVE_REPLICA_RESUMES,
                          cause="resume") == res0 + 1


def test_clean_split_journals_exactly_one_handoff(disagg_app):
    """Double-billing regression, clean leg: an un-killed split request
    ends with EXACTLY one journaled handoff entry."""
    from ray_tpu.serve.proxy import _Router

    _wait_group()
    s = _Router().stream("dllm", "generate", dict(PAYLOAD))
    s._timeout = 120.0
    assert len(list(s)) == 8
    assert len(s.journal.handoffs) == 1
    assert s.journal.handoffs[0]["attempt"] == 0
    assert s.journal.resumes == 0


def test_resumed_marker_surfaces_on_sampled_split_death(disagg_app):
    """A SAMPLED split request whose decode replica dies after the
    journaled handoff must tell the client: the x-ray-tpu-resumed
    header rides the HTTP response."""
    _wait_group()
    chaos.configure("kill_transfer:stage=import", seed=13)
    toks, marker = _http_stream(disagg_app, {
        **PAYLOAD, "sampling": {"temperature": 0.7}})
    chaos.configure(None)
    assert toks  # the replayed draw still streams a completion
    assert marker == "1"
