"""Tier-1 lint: the framework metric catalog stays self-documenting.

Every framework metric (``ray_tpu_*`` and the rpc instrumentation) must
declare a non-empty description and explicit ``tag_keys`` — the README
metrics catalog and the dashboard/CLI views are only as good as this
metadata. New framework metrics belong in ``_private/metrics_defs.py``.
"""

import inspect

import pytest

from ray_tpu._private import metrics_defs
from ray_tpu.util import metrics as metrics_mod

FRAMEWORK_PREFIXES = ("ray_tpu_", "rpc_")


def _framework_metrics():
    return [m for m in metrics_mod.all_metrics()
            if m.name.startswith(FRAMEWORK_PREFIXES)]


def test_catalog_is_nonempty_and_registered():
    catalog = [v for _, v in inspect.getmembers(metrics_defs)
               if isinstance(v, metrics_mod.Metric)]
    assert len(catalog) >= 20, "metrics catalog shrank unexpectedly"
    registered = set(map(id, metrics_mod.all_metrics()))
    assert all(id(m) in registered for m in catalog)


def test_every_framework_metric_is_documented():
    undocumented = [m.name for m in _framework_metrics()
                    if not m.description.strip()]
    assert not undocumented, (
        f"metrics without a description: {undocumented} — add one in "
        f"_private/metrics_defs.py")


def test_every_framework_metric_declares_tag_keys():
    untagged = [m.name for m in _framework_metrics() if not m.tag_keys]
    assert not untagged, (
        f"metrics without declared tag_keys: {untagged} — declare them in "
        f"_private/metrics_defs.py so series stay filterable")


def test_catalog_names_follow_conventions():
    for m in _framework_metrics():
        if not m.name.startswith("ray_tpu_"):
            continue
        if isinstance(m, metrics_mod.Counter):
            assert m.name.endswith("_total"), (
                f"counter {m.name} must end in _total")


def test_xla_and_device_memory_series_are_cataloged():
    """The XLA profiling plane's series ship described + tagged in the
    catalog (the generic lints above then cover their metadata)."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_xla_compiles_total",
        "ray_tpu_xla_compile_seconds",
        "ray_tpu_xla_retraces_total",
        "ray_tpu_xla_program_flops",
        "ray_tpu_xla_program_bytes_accessed",
        "ray_tpu_xla_achieved_flops_per_s",
        "ray_tpu_xla_achieved_bandwidth_bytes_per_s",
        "ray_tpu_xla_model_flops_utilization",
        "ray_tpu_device_mem_used_bytes",
        "ray_tpu_device_mem_peak_bytes",
        "ray_tpu_device_mem_limit_bytes",
        "ray_tpu_profile_captures_total",
    }
    missing = required - names
    assert not missing, (
        f"XLA/device-memory series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith(("ray_tpu_xla_", "ray_tpu_device_mem_")):
            assert m.description.strip() and m.tag_keys


def test_kv_arena_series_are_cataloged():
    """The paged-KV arena occupancy series (continuous-batching engine)
    ship described + tagged in the catalog — the dashboard serve panel
    and the ISSUE-6 acceptance gauges read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_cb_kv_blocks_used",
        "ray_tpu_cb_kv_blocks_total",
        "ray_tpu_cb_kv_frag_ratio",
    }
    missing = required - names
    assert not missing, (
        f"KV-arena series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_cb_"):
            assert m.description.strip() and m.tag_keys


def test_prefix_cache_series_are_cataloged():
    """The prefix-cache + affinity-routing series (radix KV-block reuse,
    cached/refcounted block gauges, router decision counters) ship
    described + tagged in the catalog — the dashboard prefix panel and
    bench_serve's prefix phase read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_cb_prefix_hit_tokens_total",
        "ray_tpu_cb_prefix_miss_tokens_total",
        "ray_tpu_cb_kv_blocks_cached",
        "ray_tpu_cb_kv_blocks_shared",
        "ray_tpu_serve_router_affinity_total",
    }
    missing = required - names
    assert not missing, (
        f"prefix-cache/affinity series missing from the catalog: "
        f"{missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_cb_prefix_"):
            assert m.description.strip() and "engine" in m.tag_keys
        if m.name == "ray_tpu_serve_router_affinity_total":
            assert {"deployment", "decision"} <= set(m.tag_keys)


def test_spec_decode_series_are_cataloged():
    """The speculative-decode series (drafted/accepted token counters,
    windowed accept-rate gauge, live draft depth k) ship described +
    tagged in the catalog — the dashboard 'Serve / speculative decode'
    panel and bench_serve's spec phase read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_cb_spec_draft_tokens_total",
        "ray_tpu_cb_spec_accepted_tokens_total",
        "ray_tpu_cb_spec_accept_rate",
        "ray_tpu_cb_spec_k",
    }
    missing = required - names
    assert not missing, (
        f"speculative-decode series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_cb_spec_"):
            assert m.description.strip() and "engine" in m.tag_keys
    # The dashboard renders the plane beside the KV-arena panel.
    from ray_tpu import dashboard

    assert 'id="spec"' in dashboard._INDEX_HTML


def test_serve_request_series_are_cataloged():
    """The request-path observability series (TTFT decomposition, TPOT,
    outcomes, event-buffer drops) ship described + tagged in the catalog
    — the dashboard latency-breakdown panel and bench_serve's
    ttft_breakdown baseline read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_serve_request_ttft_seconds",
        "ray_tpu_serve_request_queue_seconds",
        "ray_tpu_serve_request_arena_wait_seconds",
        "ray_tpu_serve_request_prefill_seconds",
        "ray_tpu_serve_request_tpot_seconds",
        "ray_tpu_serve_request_outcomes_total",
        "ray_tpu_events_dropped_total",
    }
    missing = required - names
    assert not missing, (
        f"request-path series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_serve_request_"):
            assert m.description.strip() and m.tag_keys
            if m.name != "ray_tpu_serve_request_latency_seconds":
                # Attribution tags: per-deployment AND per-tenant.
                assert {"deployment", "tenant"} <= set(m.tag_keys), m.name


@pytest.mark.parametrize("name,tag,span", [
    ("ray_tpu_cb_step_lock_wait_ms", "engine", "engine.lock_wait"),
    ("ray_tpu_cb_step_admit_ms", "engine", "engine.admit"),
    ("ray_tpu_cb_prefill_ms", "engine", "engine.prefill"),
    ("ray_tpu_cb_step_upload_ms", "engine", "engine.upload"),
    ("ray_tpu_cb_step_account_ms", "engine", "engine.account"),
    ("ray_tpu_cb_step_apply_ms", "engine", "engine.apply"),
    ("ray_tpu_serve_request_lock_wait_seconds", "deployment",
     "engine.submit_wait"),
])
def test_engine_phase_series_are_cataloged(name, tag, span):
    """The engine thread's phase counters and the head of the TTFT chain
    (benchmark/metrics/*.json read them by these names): a histogram
    each, described, tagged, documented in the README catalog, and the
    description names the span that is the same measurement."""
    import pathlib

    import ray_tpu

    (m,) = [m for m in _framework_metrics() if m.name == name]
    assert isinstance(m, metrics_mod.Histogram)
    assert tag in m.tag_keys and span in m.description
    readme = (pathlib.Path(ray_tpu.__file__).resolve().parent.parent
              / "README.md").read_text()
    assert name in readme and span in readme


def test_train_ingest_series_are_cataloged():
    """The training input-pipeline series (prefetch stall/occupancy,
    data-plane bytes) ship described + tagged in the catalog — the
    dashboard 'Train / input pipeline' panel and bench.py's input-stall
    fraction read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_train_input_stall_seconds",
        "ray_tpu_train_prefetch_buffer_occupancy",
        "ray_tpu_train_ingest_bytes_total",
    }
    missing = required - names
    assert not missing, (
        f"train-ingest series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name in required:
            assert m.description.strip() and "iterator" in m.tag_keys


def test_serve_ingress_and_engine_admission_emit_spans():
    """The request-path trace is only connected if BOTH ends emit: the
    serve ingresses must mint the request context + close the ingress
    span, and the engine admission path must record the lifecycle
    (queue/arena-wait/prefill spans + TTFT decomposition). A refactor
    that drops either silently severs every request trace, so lint the
    entry points."""
    import pathlib

    import ray_tpu
    from ray_tpu.models.continuous_batching import ContinuousBatcher
    from ray_tpu.serve import proxy

    root = pathlib.Path(ray_tpu.__file__).parent
    proxy_src = (root / "serve" / "proxy.py").read_text()
    # Every ingress (HTTP route + both gRPC handlers) goes through the
    # shared mint/close helpers.
    assert proxy_src.count("ingress_request_context(") >= 4
    assert '"serve.ingress"' in proxy_src
    engine_src = (root / "models" / "continuous_batching.py").read_text()
    for marker in ('"engine.queue"', '"engine.prefill"',
                   '"engine.decode_window"', "_note_first_token("):
        assert marker in engine_src, marker
    # And the engine API actually exposes the lifecycle surface.
    assert hasattr(ContinuousBatcher, "pressure_snapshot")
    assert callable(getattr(proxy, "ingress_request_context"))


def test_serve_replica_lifecycle_series_are_cataloged():
    """The serve failure-plane series (controller drains by cause,
    observed replica deaths, in-flight request resumes, drain-duration
    histogram) ship described + tagged in the catalog — the dashboard
    'Serve / replica lifecycle' panel and the ISSUE-13 acceptance
    criteria read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_serve_replica_drains_total",
        "ray_tpu_serve_replica_deaths_total",
        "ray_tpu_serve_replica_resumes_total",
        "ray_tpu_serve_drain_seconds",
    }
    missing = required - names
    assert not missing, (
        f"serve replica-lifecycle series missing from the catalog: "
        f"{missing}")
    for m in _framework_metrics():
        if m.name in required:
            assert m.description.strip() and "deployment" in m.tag_keys
        if m.name.startswith("ray_tpu_serve_replica_"):
            # The failure classification rides the cause tag
            # (scale_down/preemption vs died/drain vs
            # resubmit/resume/drain_reject).
            assert "cause" in m.tag_keys, m.name
        if m.name == "ray_tpu_serve_drain_seconds":
            assert "outcome" in m.tag_keys
    # The dashboard renders the plane.
    from ray_tpu import dashboard

    assert 'id="lifecycle"' in dashboard._INDEX_HTML


def test_router_dispatch_paths_handle_actor_death_through_the_journal():
    """Source lint: EVERY router dispatch path that catches
    ``ActorDiedError`` must recover through the journal plane
    (serve/recovery.py) — budgeted, tagged, typed-terminal — never a
    bare fixed-count retry. A blind retry silently re-executes calls a
    dead replica may have half-run and un-counts the recovery, so the
    lint pins each catch site to its journal routing."""
    import pathlib

    import ray_tpu
    from ray_tpu.serve import proxy as proxy_mod
    from ray_tpu.serve import recovery

    root = pathlib.Path(ray_tpu.__file__).parent / "serve"
    # Catch sites allowed per file: the enclosing function must be a
    # known recovery point (router dispatch paths) or a controller
    # bookkeeping probe (which tears down, never retries).
    allowed = {
        "api.py": {"result",            # unary journal-gated retry
                   "_reconcile_locked",  # controller death accounting
                   "_advance_drains"},   # died-while-draining accounting
        "recovery.py": {"__next__",      # streaming journal
                        "_prefill_attempt"},  # disagg unary prefill leg
    }
    for path in sorted(root.glob("*.py")):
        src = path.read_text().splitlines()
        current_def = "<module>"
        for i, line in enumerate(src):
            stripped = line.strip()
            if stripped.startswith(("def ", "async def ")):
                current_def = stripped.split("def ", 1)[1].split("(")[0]
            if "except" in stripped and "ActorDiedError" in stripped:
                ok = current_def in allowed.get(path.name, set())
                assert ok, (
                    f"{path.name}:{i + 1} catches ActorDiedError in "
                    f"{current_def!r} outside the journal plane — route "
                    f"it through serve/recovery.py")
    # The dispatch paths actually use the journal surface (a rename
    # that severs them should fail here, not silently drop recovery).
    api_src = (root / "api.py").read_text()
    assert "recovery.max_resumes()" in api_src
    assert "recovery.note_unary_retry" in api_src
    assert "recovery.exhausted_error" in api_src
    assert "attempts >= 5" not in api_src, "the blind 5x retry is back"
    rec_src = (root / "recovery.py").read_text()
    assert "_resume_after_death" in rec_src
    # The ingress streaming path dispatches through the journal.
    import inspect

    assert "RecoverableStream" in inspect.getsource(proxy_mod._Router.stream)
    assert callable(recovery.max_resumes)
    assert hasattr(recovery.RequestJournal, "resume_payload")


def test_disagg_kv_transfer_series_are_cataloged_and_pinned():
    """The disaggregated prefill/decode handoff plane (ISSUE 20): the
    KV-transfer series ship described + tagged with the hop direction,
    the handoff ledger counter carries the outcome classification, request
    histograms carry the role tag, and a SOURCE LINT pins every
    cross-replica export/import call site to the journal-gated helper
    (serve/kv_transfer.py) — a bare channel write of arena bytes beside
    the journal would break exactly-once billing silently."""
    import inspect
    import pathlib

    import ray_tpu

    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_serve_kv_transfer_seconds",
        "ray_tpu_serve_kv_transfer_bytes_total",
        "ray_tpu_serve_kv_transfer_blocks_total",
        "ray_tpu_serve_handoff_total",
    }
    missing = required - names
    assert not missing, (
        f"disagg KV-transfer series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_serve_kv_transfer_"):
            # export / channel / import: the three legs of the hop.
            assert m.description.strip() and "direction" in m.tag_keys, \
                m.name
        if m.name == "ray_tpu_serve_handoff_total":
            # ok / prefill_died / decode_died / crc_mismatch.
            assert "outcome" in m.tag_keys
        if m.name == "ray_tpu_serve_request_ttft_seconds":
            # Role-sliced latency: prefill vs decode vs colocated fleets.
            assert "role" in m.tag_keys
    # Source lint: the engine's export_kv_payload / import_kv_payload
    # are called ONLY from serve/kv_transfer.py (besides their own
    # definitions) — every transfer rides the journal-gated helper.
    root = pathlib.Path(ray_tpu.__file__).parent
    exempt = {"models/continuous_batching.py",  # defines them
              "serve/kv_transfer.py"}           # the one legal caller
    offenders = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel in exempt:
            continue
        src = path.read_text()
        for site in ("export_kv_payload", "import_kv_payload"):
            if site in src:
                offenders.append(f"{rel}: {site}")
    assert not offenders, (
        f"KV arena bytes must cross replicas only through "
        f"serve/kv_transfer.py: {offenders}")
    # The helper enforces the journal gate, and the router's streaming
    # path classifies into the disagg journal stream.
    from ray_tpu.serve import kv_transfer
    from ray_tpu.serve import proxy as proxy_mod

    assert "journaled" in inspect.getsource(kv_transfer.receive_handoff)
    assert "DisaggRecoverableStream" in \
        inspect.getsource(proxy_mod._Router.stream)
    # The dashboard renders the plane.
    from ray_tpu import dashboard

    assert 'id="disagg"' in dashboard._INDEX_HTML


def test_train_elasticity_series_are_cataloged():
    """The elastic-trainer series (restarts by cause, current world
    size, failure-to-first-report recovery time) ship described + tagged
    in the catalog — the dashboard 'Train / elasticity' panel and the
    ISSUE-10 acceptance criteria read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_train_restarts_total",
        "ray_tpu_train_world_size",
        "ray_tpu_train_recovery_seconds",
    }
    missing = required - names
    assert not missing, (
        f"train-elasticity series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name in required:
            assert m.description.strip() and "trainer" in m.tag_keys
        if m.name == "ray_tpu_train_restarts_total":
            # The failure classification rides the cause tag
            # (worker_lost/hang/preemption/resize/user).
            assert "cause" in m.tag_keys


def test_train_goodput_series_are_cataloged():
    """The training-path observability series (goodput ledger counters/
    fractions, per-rank step-time histogram, straggler flag) ship
    described + tagged in the catalog — the dashboard 'Train / goodput
    & stragglers' panel and the ISSUE-12 acceptance criteria read
    them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_train_goodput_seconds_total",
        "ray_tpu_train_goodput_fraction",
        "ray_tpu_train_rank_step_seconds",
        "ray_tpu_train_straggler",
    }
    missing = required - names
    assert not missing, (
        f"train-goodput series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name in required:
            assert m.description.strip() and "trainer" in m.tag_keys
        if m.name.startswith("ray_tpu_train_goodput_"):
            assert "component" in m.tag_keys, m.name
        if m.name in ("ray_tpu_train_rank_step_seconds",
                      "ray_tpu_train_straggler"):
            assert "rank" in m.tag_keys, m.name


def test_train_step_loop_and_recovery_emit_spans():
    """The train trace is only connected if every layer emits: the
    worker session must record per-step timings and own a goodput
    ledger, the instrumented sites must attribute their components, and
    the controller must emit the run/attempt/step-window/recovery span
    tree. A refactor that drops any of these silently severs every
    training trace (the serve twin of this lint guards the request
    path), so lint the entry points."""
    import pathlib

    import ray_tpu
    from ray_tpu.train import goodput
    from ray_tpu.train.elastic import RecoveryTrace
    from ray_tpu.train.trainer import JaxTrainer

    root = pathlib.Path(ray_tpu.__file__).parent
    trainer_src = (root / "train" / "trainer.py").read_text()
    for marker in ('"train.run"', '"train.attempt"',
                   '"train.step_window"', "RecoveryTrace("):
        assert marker in trainer_src, marker
    elastic_src = (root / "train" / "elastic.py").read_text()
    for marker in ('"train.recovery"',
                   '"train.recovery.restore_first_step"'):
        assert marker in elastic_src, marker
    # Worker side: step timings ride the report queue, the session owns
    # the attempt ledger, and each instrumented site attributes its
    # component.
    assert "step_timing" in (root / "train" / "session.py").read_text()
    assert "ledger" in (root / "train" /
                        "backend_executor.py").read_text()
    assert 'note_ambient("input_stall"' in (
        root / "train" / "ingest.py").read_text()
    assert 'note("sync"' in (root / "train" / "loop.py").read_text()
    plane_src = (root / "checkpoint" / "plane.py").read_text()
    assert 'note_ambient("ckpt_block"' in plane_src
    assert 'note_ambient("recovery"' in plane_src
    # And the API surface the controller drives.
    assert callable(goodput.note_ambient)
    assert hasattr(goodput.GoodputLedger, "snapshot")
    assert hasattr(goodput.StragglerDetector, "observe")
    assert hasattr(JaxTrainer, "goodput_summary")
    assert hasattr(RecoveryTrace, "close")
    # The dashboard renders the plane.
    from ray_tpu import dashboard

    assert 'id="goodput"' in dashboard._INDEX_HTML


def test_checkpoint_plane_series_are_cataloged():
    """The checkpoint plane's series (ray_tpu/checkpoint/) ship described
    + tagged in the catalog, including the acceptance-criteria
    ``ray_tpu_ckpt_block_ms`` step-blocking gauge."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_ckpt_block_ms",
        "ray_tpu_ckpt_save_seconds",
        "ray_tpu_ckpt_restore_seconds",
        "ray_tpu_ckpt_bytes_total",
        "ray_tpu_ckpt_saves_total",
        "ray_tpu_ckpt_preempt_notices_total",
    }
    missing = required - names
    assert not missing, (
        f"checkpoint-plane series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_ckpt_"):
            assert m.description.strip() and m.tag_keys


# Framework-owned jax.jit call sites must go through the instrumented
# wrapper (ray_tpu._private.xla_monitor.instrument) so every compile,
# retrace and cost analysis is observed. Intentional raw jits are
# allowlisted here WITH a reason: a whole file, or ``file::function`` for
# ONE decorated function (the rest of its file stays linted).
RAW_JIT_ALLOWLIST = {
    # The wrapper itself wraps jax.jit.
    "_private/xla_monitor.py": "the instrumented wrapper's own jit",
    # RL host loops: many tiny per-algorithm jits driven at env cadence,
    # not cluster-serving hot paths; instrumenting them would flood the
    # program registry without a roofline story.
    "rllib/env_runner.py": "RL env-loop jits",
    "rllib/multi_agent.py": "RL env-loop jits",
    "rllib/core.py": "RL learner jits",
    # Not a program: an inner jit kept for its TRACE cache. The kernel's
    # body is unrolled (every substitution step of four heads), and a
    # prefill program calls it once a run of linear layers; it only runs
    # inside ``cb_prefill``, which is instrumented (PR 44).
    "ops/gated_delta.py::_gdn_chunk_scan_fused":
        "gdn_chunk_scan's wrapper, traced once a shape",
}


def test_framework_jits_go_through_the_instrumented_wrapper():
    import pathlib
    import re

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    offenders = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel in RAW_JIT_ALLOWLIST:
            continue
        lines = path.read_text().splitlines()
        for lineno, line in enumerate(lines, start=1):
            code = line.split("#", 1)[0]
            if not re.search(r"\bjax\.jit\b", code):
                continue
            # A decorator's function is the next ``def`` below it.
            below = re.search(r"^def (\w+)", "\n".join(lines[lineno:]), re.M)
            if (code.startswith("@") and below
                    and f"{rel}::{below[1]}" in RAW_JIT_ALLOWLIST):
                continue
            offenders.append(f"{rel}:{lineno}")
    assert not offenders, (
        f"raw jax.jit call sites outside the allowlist: {offenders} — "
        f"route them through ray_tpu._private.xla_monitor.instrument "
        f"(or allowlist them with a reason in test_metrics_lint.py)")


def test_engine_tick_and_prefill_entry_points_are_instrumented():
    """The continuous-batching hot-loop entry points (tick + prefill)
    must stay under ``xla_monitor.instrument`` — their
    compiles, retraces, and cost analyses feed the decode-roofline
    regression harness, so an accidental downgrade to a raw jit is a
    silent observability hole."""
    import jax.numpy as jnp

    from ray_tpu._private.xla_monitor import InstrumentedJit
    from ray_tpu.models import llama
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    eng = ContinuousBatcher(cfg, num_slots=2, max_len=64)
    assert isinstance(eng._tick, InstrumentedJit)
    assert isinstance(eng._prefill, InstrumentedJit)


def test_pool_and_autoscaler_series_are_cataloged():
    """The chip-pool arbiter + autoscaler-resilience series ship
    described + tagged in the catalog — the dashboard 'Pool / chip
    leases & handoffs' panel, `ray-tpu pool status`, and the ISSUE-15
    acceptance criteria read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_pool_chips",
        "ray_tpu_pool_leases",
        "ray_tpu_pool_handoffs_total",
        "ray_tpu_pool_handoff_seconds",
        "ray_tpu_pool_slo_reversals_total",
        "ray_tpu_pool_invariant_violations_total",
        "ray_tpu_autoscaler_allocation_failures_total",
        "ray_tpu_autoscaler_consecutive_tick_failures",
        "ray_tpu_serve_autoscale_decisions_total",
    }
    missing = required - names
    assert not missing, (
        f"pool/autoscaler series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name in required:
            assert m.description.strip() and m.tag_keys, m.name
        if m.name == "ray_tpu_pool_chips":
            assert "owner" in m.tag_keys
        if m.name == "ray_tpu_pool_handoffs_total":
            assert {"direction", "outcome"} <= set(m.tag_keys)
        if m.name == "ray_tpu_pool_slo_reversals_total":
            assert {"action", "signal"} <= set(m.tag_keys)
        if m.name == "ray_tpu_serve_autoscale_decisions_total":
            assert {"deployment", "direction", "signal"} <= set(m.tag_keys)
        if m.name.startswith("ray_tpu_autoscaler_"):
            assert "provider" in m.tag_keys, m.name
    # The dashboard renders the plane beside Train / elasticity.
    from ray_tpu import dashboard

    assert 'id="pool"' in dashboard._INDEX_HTML


def test_arbiter_ledger_transitions_are_journaled():
    """Source lint: EVERY KV mutation in the arbiter goes through the
    ledger's journaled helpers (_journal_put/_journal_del) or the KV
    store adapters they call — never a bare internal_kv/KvPut write. A
    bare write could move chips without a journal record, and the whole
    crash-resume story (and the conservation invariant) hangs off the
    journal being complete."""
    import pathlib

    import ray_tpu
    from ray_tpu.autoscaler import arbiter

    path = pathlib.Path(ray_tpu.__file__).parent / "autoscaler" / \
        "arbiter.py"
    allowed = {"_journal_put", "_journal_del",   # the ledger chokepoints
               "put", "delete"}                  # the KV store adapters
    current_def = "<module>"
    for i, line in enumerate(path.read_text().splitlines()):
        stripped = line.strip()
        if stripped.startswith(("def ", "async def ")):
            current_def = stripped.split("def ", 1)[1].split("(")[0]
        code = stripped.split("#", 1)[0]
        if "internal_kv_put(" in code or "internal_kv_del(" in code or \
                ".kv.put(" in code or ".kv.delete(" in code or \
                "KvPut(" in code:
            assert current_def in allowed, (
                f"arbiter.py:{i + 1} writes the KV in {current_def!r} "
                f"outside the journaled helpers — route it through "
                f"PoolLedger._journal_put/_journal_del")
    # The chokepoints and the state machine actually exist.
    assert callable(arbiter.PoolLedger._journal_put)
    assert callable(arbiter.PoolLedger._journal_del)
    src = path.read_text()
    for marker in ("_LEASE_TRANSITIONS", "InvalidLeaseTransition",
                   "def verify", "def advance"):
        assert marker in src, marker
    # Every advance() call journals through the validated helper (no
    # parallel transition path).
    assert "self._journal_put(f\"lease/" in src


def _funcs_emit_flight(path, funcs, window: int = 60):
    """Assert each named function body contains a flight-recorder
    ``_events.emit(`` within ``window`` lines of its def — the causal
    chain is only connected if these sites keep emitting."""
    lines = path.read_text().splitlines()
    for fn in funcs:
        hits = [i for i, ln in enumerate(lines)
                if ln.strip().startswith(("def ", "async def "))
                and ln.strip().split("def ", 1)[1].startswith(fn + "(")]
        assert hits, f"{path.name}: function {fn!r} vanished"
        assert any("_events.emit(" in "\n".join(lines[i:i + window])
                   for i in hits), (
            f"{path.name}: {fn!r} no longer records a flight event — "
            f"the `ray-tpu why` causal chain breaks without it")


def test_flight_recorder_series_and_emit_sites_are_pinned():
    """The flight recorder only answers ``ray-tpu why`` if every
    control plane actually emits: the event counter/drop accounting
    ship in the catalog, and source lints pin the arbiter's journaled
    lease transitions, the serve controller's drain begin/advance, and
    elastic recovery close to their ``_events.emit`` calls — a refactor
    dropping one silently severs the causal chain."""
    import pathlib

    import ray_tpu

    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_events_total",
        "ray_tpu_events_dropped_total",
    }
    missing = required - names
    assert not missing, (
        f"flight-recorder series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name == "ray_tpu_events_total":
            assert m.description.strip() and "type" in m.tag_keys
        if m.name == "ray_tpu_events_dropped_total":
            assert "buffer" in m.tag_keys

    root = pathlib.Path(ray_tpu.__file__).parent
    # Arbiter: every journaled lease transition (create/advance) and the
    # SLO reversal record emit beside their _journal_put.
    _funcs_emit_flight(root / "autoscaler" / "arbiter.py",
                       ["create_lease", "advance", "record_reversal"])
    # Serve controller: drains emit at begin AND at settle.
    _funcs_emit_flight(root / "serve" / "api.py",
                       ["_begin_drain", "_advance_drains"],
                       window=80)
    # Elastic recovery: RecoveryTrace.close records cause + outcome
    # BEFORE the tracing gate (flight events flow with tracing off).
    elastic_src = (root / "train" / "elastic.py").read_text()
    close_body = elastic_src.split("def close(", 1)[1]
    emit_at = close_body.index("_events.emit(")
    gate_at = close_body.index("tracing.enabled()")
    assert emit_at < gate_at, (
        "train.recovery flight emit moved behind the tracing gate — "
        "recoveries would vanish from the recorder with tracing off")
    # Preemption notices carry their event id cluster-wide.
    preempt_src = (root / "checkpoint" / "preempt.py").read_text()
    assert 'notice["notice_id"]' in preempt_src
    # The GCS probe-before-reap verdicts and chaos injections emit.
    gcs_src = (root / "_private" / "gcs" / "server.py").read_text()
    assert '"gcs.probe"' in gcs_src and '"gcs.node_dead"' in gcs_src
    assert '"chaos.inject"' in (root / "_private" /
                                "chaos.py").read_text()
    # The dashboard renders the plane and the CLI walks it.
    from ray_tpu import dashboard

    assert 'id="flight"' in dashboard._INDEX_HTML
    assert "/api/v1/events" in dashboard._INDEX_HTML
    from ray_tpu.scripts import cli

    assert callable(cli.cmd_why)


def test_head_control_plane_series_are_cataloged():
    """The head-load observability series (per-namespace KV accounting,
    pubsub fan-out/drops, WAL health, RPC saturation + client retries)
    ship described + tagged in the catalog — the dashboard 'Head /
    control plane' panel, `ray-tpu head top`, and bench_control.py read
    them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_gcs_kv_ops_total",
        "ray_tpu_gcs_kv_bytes_total",
        "ray_tpu_gcs_pubsub_published_total",
        "ray_tpu_gcs_pubsub_fanout_seconds",
        "ray_tpu_gcs_pubsub_queue_depth",
        "ray_tpu_gcs_pubsub_dropped_total",
        "ray_tpu_gcs_wal_queue_depth",
        "ray_tpu_gcs_wal_watermark_lag",
        "ray_tpu_gcs_wal_fsync_seconds",
        "ray_tpu_gcs_wal_compaction_seconds",
        "ray_tpu_gcs_wal_sync_timeouts_total",
        "ray_tpu_gcs_health_tick_seconds",
        "ray_tpu_gcs_health_probe_backlog",
        "ray_tpu_rpc_queue_wait_seconds",
        "ray_tpu_rpc_executor_occupancy",
        "ray_tpu_rpc_active_streams",
        "ray_tpu_rpc_client_retries_total",
    }
    missing = required - names
    assert not missing, (
        f"head control-plane series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if m.name.startswith("ray_tpu_gcs_kv_"):
            assert {"op", "namespace"} <= set(m.tag_keys), m.name
        if m.name.startswith("ray_tpu_gcs_pubsub_"):
            assert "channel" in m.tag_keys, m.name
        if m.name == "ray_tpu_gcs_pubsub_dropped_total":
            # Slow-subscriber sheds must be attributable.
            assert "subscriber" in m.tag_keys
        if m.name.startswith("ray_tpu_gcs_wal_"):
            assert "backend" in m.tag_keys, m.name
        if m.name in ("ray_tpu_rpc_queue_wait_seconds",
                      "ray_tpu_rpc_executor_occupancy"):
            assert "service" in m.tag_keys, m.name
        if m.name == "ray_tpu_rpc_client_retries_total":
            assert {"service", "method", "reason"} <= set(m.tag_keys)
    # The dashboard renders the plane and the CLI summarises it.
    from ray_tpu import dashboard
    from ray_tpu.scripts import cli

    assert 'id="head"' in dashboard._INDEX_HTML
    assert callable(cli.cmd_head)


def test_rl_weight_sync_series_are_cataloged():
    """The RL post-training loop's series (sync latency/bytes by path,
    trainer/generator version gauges, rollout staleness, tick-boundary
    swaps by cause, shed-with-attribution) ship described + tagged in
    the catalog — the dashboard 'RL / weight sync & rollout' panel and
    bench.py's rl_loop phase read them."""
    names = {m.name for m in _framework_metrics()}
    required = {
        "ray_tpu_rl_weight_sync_seconds",
        "ray_tpu_rl_weight_sync_bytes_total",
        "ray_tpu_rl_weight_sync_version",
        "ray_tpu_rl_rollout_staleness",
        "ray_tpu_rl_weight_swaps_total",
        "ray_tpu_rl_weight_sync_shed_total",
    }
    missing = required - names
    assert not missing, (
        f"RL weight-sync series missing from the catalog: {missing}")
    for m in _framework_metrics():
        if not m.name.startswith("ray_tpu_rl_"):
            continue
        assert m.description.strip() and "run" in m.tag_keys, m.name
        if m.name in ("ray_tpu_rl_weight_sync_seconds",
                      "ray_tpu_rl_weight_sync_bytes_total"):
            # Fast vs slow path attribution (publish/subscribe/fallback).
            assert "path" in m.tag_keys, m.name
        if m.name == "ray_tpu_rl_weight_sync_version":
            # Trainer-vs-generator version gap IS the sync lag.
            assert "role" in m.tag_keys
        if m.name == "ray_tpu_rl_weight_swaps_total":
            assert "cause" in m.tag_keys
        if m.name == "ray_tpu_rl_weight_sync_shed_total":
            # Sheds must name the lagging subscriber.
            assert "subscriber" in m.tag_keys
    # The dashboard renders the plane.
    from ray_tpu import dashboard

    assert 'id="rl"' in dashboard._INDEX_HTML


def test_generator_param_swaps_ride_the_tick_boundary():
    """Source lint: the serving engine's live params may be assigned only
    at init and through ``ContinuousBatcher.swap_params`` (which callers
    must invoke holding tick exclusion), and the only swap_params call
    site in the serve/llm/rllib planes is
    ``ContinuousLlamaDeployment.swap_weights`` — the lock-holding
    tick-boundary entry point. A mid-tick params write would hand one
    decode tick a torn weight set; this pins the invariant the RL sync
    plane's in-flight-requests-survive guarantee rests on."""
    import pathlib
    import re

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    # 1) Engine side: every `self.params` store in the batcher module
    # lives in __init__ or swap_params.
    engine_path = root / "models" / "continuous_batching.py"
    allowed = {"__init__", "swap_params"}
    current_def = "<module>"
    store = re.compile(r"self\.params\s*=[^=]")
    for i, line in enumerate(engine_path.read_text().splitlines()):
        stripped = line.strip()
        if stripped.startswith(("def ", "async def ")):
            current_def = stripped.split("def ", 1)[1].split("(")[0]
        if store.search(stripped.split("#", 1)[0]):
            assert current_def in allowed, (
                f"continuous_batching.py:{i + 1} assigns self.params in "
                f"{current_def!r} — live params may only change through "
                f"swap_params under tick exclusion")
    # 2) Caller side: serve/, llm/ and rllib/ reach swap_params only
    # through the deployment's lock-holding swap_weights.
    for sub in ("serve", "llm", "rllib"):
        for path in sorted((root / sub).rglob("*.py")):
            current_def = "<module>"
            for i, line in enumerate(path.read_text().splitlines()):
                stripped = line.strip()
                if stripped.startswith(("def ", "async def ")):
                    current_def = stripped.split(
                        "def ", 1)[1].split("(")[0]
                code = stripped.split("#", 1)[0]
                if ".swap_params(" in code or \
                        re.search(r"\.batcher\.params\s*=", code):
                    assert current_def == "swap_weights", (
                        f"{sub}/{path.name}:{i + 1} swaps generator "
                        f"params in {current_def!r} — route it through "
                        f"ContinuousLlamaDeployment.swap_weights (the "
                        f"tick-boundary entry point)")
    # The entry points themselves exist and hold the contract.
    from ray_tpu.llm import ContinuousLlamaDeployment
    from ray_tpu.models.continuous_batching import ContinuousBatcher

    assert callable(ContinuousBatcher.swap_params)
    cls = getattr(ContinuousLlamaDeployment, "_cls_or_fn",
                  ContinuousLlamaDeployment)
    assert callable(getattr(cls, "swap_weights"))
    llm_src = (root / "llm" / "__init__.py").read_text()
    swap_body = llm_src.split("def swap_weights(", 1)[1]
    lock_at = swap_body.index("with self._lock:")
    call_at = swap_body.index("swap_params(")
    assert lock_at < call_at, (
        "swap_weights no longer takes the engine lock before "
        "swap_params — the tick-boundary guarantee is gone")
    """Source lint: EVERY function in gcs/server.py that mutates the raw
    ``self._kv`` dict must call ``self._account_kv(`` (or be a recovery
    path that replays already-accounted history), and all four Kv*
    handlers must account. A mutation outside the helper silently skews
    the per-namespace ops/bytes ledger that capacity planning
    (bench_control's knee) is read against."""
    import pathlib
    import re

    import ray_tpu

    path = pathlib.Path(ray_tpu.__file__).parent / "_private" / "gcs" / \
        "server.py"
    src = path.read_text()
    # Recovery/bootstrap paths replay history whose original mutations
    # were accounted when they first happened.
    replay_allowed = {"__init__", "_load_snapshot", "_apply_wal_record"}
    mutation = re.compile(
        r"self\._kv\[[^\]]*\]\s*=|self\._kv\.(pop|setdefault|update|"
        r"clear)\(")
    bodies: dict = {}
    current_def = "<module>"
    for line in src.splitlines():
        stripped = line.strip()
        if stripped.startswith(("def ", "async def ")):
            current_def = stripped.split("def ", 1)[1].split("(")[0]
        bodies.setdefault(current_def, []).append(
            stripped.split("#", 1)[0])
    for fn, lines in bodies.items():
        body = "\n".join(lines)
        if not mutation.search(body):
            continue
        if fn in replay_allowed:
            continue
        assert "self._account_kv(" in body, (
            f"gcs/server.py: {fn!r} mutates self._kv without calling "
            f"self._account_kv — per-namespace accounting would drift")
    # The four handlers all account (KvGet via its accounting wrapper).
    for handler in ("KvPut", "KvGet", "KvDel", "KvKeys"):
        assert handler in bodies, f"handler {handler} vanished"
        assert "self._account_kv(" in "\n".join(bodies[handler]), (
            f"{handler} no longer routes through self._account_kv")
    # Namespace labels stay bounded: user namespaces collapse.
    helper = "\n".join(bodies.get("_account_kv", []))
    assert '"user"' in helper, (
        "_account_kv lost the user-namespace cardinality collapse")
