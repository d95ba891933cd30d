"""Framework metric catalog: every built-in Counter/Gauge/Histogram.

One module owns every framework metric so the catalog stays greppable and
self-documenting — a tier-1 lint (tests/test_metrics_lint.py) asserts each
``ray_tpu_*`` metric carries a non-empty description and declared
``tag_keys``. Instrumented code imports from here; metric names, tags and
units are documented in README "Observability".

Units follow Prometheus conventions: ``_total`` counters, ``_seconds`` /
``_bytes`` gauges and histograms.
"""

from __future__ import annotations

from ray_tpu.util.metrics import Counter, Gauge, Histogram

# ------------------------------------------------------ scheduler (L2 core)
TASKS_SUBMITTED = Counter(
    "ray_tpu_scheduler_tasks_submitted_total",
    "Tasks submitted by this process (normal and actor tasks)",
    ("kind",))
TASKS_COMPLETED = Counter(
    "ray_tpu_scheduler_tasks_completed_total",
    "Task results applied by this process, by terminal status",
    ("status",))
LEASE_REQUESTS = Counter(
    "ray_tpu_scheduler_lease_requests_total",
    "Worker-lease negotiation outcomes (granted/spillback/retry)",
    ("result",))
LEASE_CACHE = Counter(
    "ray_tpu_scheduler_lease_cache_total",
    "Lease-cache lookups on the submit path (hit/miss)",
    ("outcome",))
LEASE_LATENCY = Histogram(
    "ray_tpu_scheduler_lease_latency_seconds",
    "Wall time to negotiate a fresh worker lease",
    tag_keys=("kind",))
PUSH_LATENCY = Histogram(
    "ray_tpu_scheduler_push_latency_seconds",
    "Wall time of one task push to a leased worker (execution included)",
    tag_keys=("mode",))
ASYNC_FUTURES = Counter(
    "ray_tpu_scheduler_async_futures_total",
    "ObjectRef futures created, by resolution path "
    "(inline/callback/poll)",
    ("path",))

# ------------------------------------------------- node manager (L1 raylet)
NODE_WORKERS = Gauge(
    "ray_tpu_node_workers",
    "Worker processes on this node by state (idle/busy/total)",
    ("node_id", "state"))
NODE_LEASE_QUEUE = Gauge(
    "ray_tpu_node_lease_queue_depth",
    "Lease RPCs queued server-side waiting for resources",
    ("node_id",))
NODE_LEASES_GRANTED = Counter(
    "ray_tpu_node_leases_granted_total",
    "Worker leases granted by this node manager",
    ("node_id",))
NODE_OOM_KILLS = Counter(
    "ray_tpu_node_oom_kills_total",
    "Task workers killed by the node memory monitor",
    ("node_id",))
NODE_MEM_AVAILABLE = Gauge(
    "ray_tpu_node_mem_available_bytes",
    "Host MemAvailable sampled from /proc/meminfo",
    ("node_id",))
NODE_LOADAVG = Gauge(
    "ray_tpu_node_loadavg_1m",
    "Host 1-minute load average",
    ("node_id",))

# ------------------------------------------------------ object store (L1)
STORE_PUTS = Counter(
    "ray_tpu_store_put_total",
    "Objects seated into (or rejected by) the node store",
    ("node_id", "outcome"))
STORE_PUT_BYTES = Counter(
    "ray_tpu_store_put_bytes_total",
    "Bytes seated into the node store",
    ("node_id",))
STORE_GETS = Counter(
    "ray_tpu_store_get_total",
    "Local store object lookups (hit/miss)",
    ("node_id", "outcome"))
STORE_USED_BYTES = Gauge(
    "ray_tpu_store_used_bytes",
    "Bytes resident in the node shared-memory store",
    ("node_id",))
STORE_OBJECTS = Gauge(
    "ray_tpu_store_objects",
    "Objects resident in the node shared-memory store",
    ("node_id",))
STORE_SPILLED = Counter(
    "ray_tpu_store_spilled_total",
    "Objects spilled to disk under memory pressure",
    ("node_id",))
STORE_SPILLED_BYTES = Counter(
    "ray_tpu_store_spilled_bytes_total",
    "Bytes spilled to disk under memory pressure",
    ("node_id",))
STORE_RESTORED = Counter(
    "ray_tpu_store_restored_total",
    "Spilled objects restored on access",
    ("node_id",))

# ------------------------------------------------------ node agent vitals
AGENT_RSS = Gauge(
    "ray_tpu_node_agent_rss_bytes",
    "Resident set size of the per-node agent process",
    ("node_id",))
AGENT_DISK_FREE = Gauge(
    "ray_tpu_node_agent_disk_free_bytes",
    "Free bytes on the spill-directory filesystem",
    ("node_id",))
AGENT_PREWARMS = Gauge(
    "ray_tpu_node_agent_prewarms",
    "Runtime-env pre-warm entries tracked by the agent, by state",
    ("node_id", "state"))

# ---------------------------------------------------------------- serve (L6)
SERVE_REQUESTS = Counter(
    "ray_tpu_serve_requests_total",
    "Requests routed per deployment (streaming included)",
    ("deployment",))
SERVE_LATENCY = Histogram(
    "ray_tpu_serve_request_latency_seconds",
    "End-to-end deployment request latency seen by the router",
    tag_keys=("deployment",))
SERVE_QUEUE_DEPTH = Gauge(
    "ray_tpu_serve_queue_depth",
    "In-flight requests this router currently has against a deployment",
    ("deployment",))
SERVE_ROUTER_AFFINITY = Counter(
    "ray_tpu_serve_router_affinity_total",
    "Prefix-affinity routing decisions: affinity (request landed on its "
    "fingerprint's home replica), overflow (home too pressured — spilled "
    "to the second rendezvous choice)",
    ("deployment", "decision"))

# ----------------------------------------------- serve replica lifecycle (L6)
# The serve failure plane: controller-initiated drains, observed replica
# deaths, and in-flight request resumes — the serve twin of the elastic
# trainer's restart/recovery series.
SERVE_REPLICA_DRAINS = Counter(
    "ray_tpu_serve_replica_drains_total",
    "Controller-initiated replica drains by cause (scale_down/preemption/"
    "delete) — a draining replica stops admitting, leaves the routing "
    "ring, finishes in-flight requests up to RAY_TPU_SERVE_DRAIN_S, then "
    "tears down",
    ("deployment", "cause"))
SERVE_REPLICA_DEATHS = Counter(
    "ray_tpu_serve_replica_deaths_total",
    "Replica deaths observed by the controller/router by cause "
    "(died: health probe found it dead; drain: it died while draining)",
    ("deployment", "cause"))
SERVE_REPLICA_RESUMES = Counter(
    "ray_tpu_serve_replica_resumes_total",
    "In-flight requests recovered after replica death, by cause: "
    "resubmit (queued/prefilling — no tokens lost), resume (mid-decode — "
    "prompt + emitted tokens replayed as a new prefill; exactly-once "
    "under greedy decoding), drain_reject (clean re-route off a draining "
    "replica, no budget consumed)",
    ("deployment", "cause"))
SERVE_DRAIN_SECONDS = Histogram(
    "ray_tpu_serve_drain_seconds",
    "Drain initiation to teardown per drained replica, by outcome "
    "(drained: in-flight work finished; deadline: RAY_TPU_SERVE_DRAIN_S "
    "expired with requests still running; died: replica died while "
    "draining)",
    boundaries=(0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                300.0),
    tag_keys=("deployment", "outcome"))

# ----------------------------------------- serve pressure autoscaling (L6)
SERVE_AUTOSCALE_DECISIONS = Counter(
    "ray_tpu_serve_autoscale_decisions_total",
    "Serve autoscaler scale intents applied, by direction (up/down) and "
    "the dominant signal that drove them (ongoing: router in-flight vs "
    "target_ongoing_requests; queue: engine queue depth vs "
    "target_queue_depth; kv: paged-KV arena starvation; shed: ingress "
    "shed rate observed since the last decision)",
    ("deployment", "direction", "signal"))

# ------------------------------------------ serve request path (L6 + engine)
# Per-request latency attribution emitted by the continuous-batching
# engine at request lifecycle boundaries: TTFT decomposes into
# queue + arena-wait + prefill (the components below sum to the TTFT
# histogram within bookkeeping noise), and TPOT is the steady decode
# cadence after the first token. Tagged per deployment and per tenant
# (the multiplexed model id) so one noisy tenant is attributable.
# ``role`` carries the engine's disaggregation role
# (prefill/decode/both) so split fleets' TTFT/TPOT separate cleanly.
_REQ_TAGS = ("deployment", "tenant", "engine", "role")
SERVE_REQ_TTFT = Histogram(
    "ray_tpu_serve_request_ttft_seconds",
    "Time to first token: engine submit to first-token fetch "
    "(= queue + arena_wait + prefill)",
    boundaries=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                5.0, 10.0, 30.0, 60.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_QUEUE = Histogram(
    "ray_tpu_serve_request_queue_seconds",
    "TTFT component: submit to admission pickup (waiting for a free "
    "KV slot / the admission loop)",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_LOCK_WAIT = Histogram(
    "ray_tpu_serve_request_lock_wait_seconds",
    "Before the TTFT clock starts: from the replica method's entry to "
    "holding the engine lock for submit (the tick thread holds that lock "
    "across each step); span engine.submit_wait",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_ARENA_WAIT = Histogram(
    "ray_tpu_serve_request_arena_wait_seconds",
    "TTFT component: time the request sat at the head of the admission "
    "queue blocked on free paged-KV arena blocks (0 when never blocked)",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_PREFILL = Histogram(
    "ray_tpu_serve_request_prefill_seconds",
    "TTFT component: prefill dispatch to first-token fetch for the "
    "request's admission batch",
    boundaries=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                5.0, 10.0, 30.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_TPOT = Histogram(
    "ray_tpu_serve_request_tpot_seconds",
    "Time per output token after the first (first token to finish over "
    "generated-token count): the steady decode cadence one request saw",
    boundaries=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_DECODE_STALL = Histogram(
    "ray_tpu_serve_request_decode_stall_seconds",
    "Seconds of a request's life after its first token that went to "
    "other requests' prefill batches, during which its stream stood "
    "still (one observation a request; stalled_s in request_breakdowns)",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    tag_keys=_REQ_TAGS)
SERVE_REQ_OUTCOMES = Counter(
    "ray_tpu_serve_request_outcomes_total",
    "Engine request terminations by outcome "
    "(finished/evicted/aborted/prefilled — prefilled is a prefill-role "
    "engine parking the request for KV handoff at its first token)",
    _REQ_TAGS + ("outcome",))

# A streamed token's way back, hop by hop, on ``time.time()`` (the request
# chain's clock). Each stream sums on locals and flushes here at its end
# and every 64 items: never once a token or once a pull. The replica's
# three are tagged with its engine, the ingress's with the deployment.
SERVE_STREAM_HANDOFF_SECONDS = Counter(
    "ray_tpu_serve_stream_handoff_seconds_total",
    "Streamed tokens' seconds from landing on the host (the engine's "
    "stamp of the tick or prefill fetch) to the stream's consumer taking "
    "them out of the request's buffer: tick thread -> the replica's "
    "event loop, which got the whole landing in one call and spread it "
    "over the streams (a synchronous caller: -> its own thread)",
    ("engine",))
SERVE_STREAM_STORE_SECONDS = Counter(
    "ray_tpu_serve_stream_store_seconds_total",
    "Streamed tokens' seconds from the stream handing them out to its "
    "consumer asking for more: the runtime stored and announced the item",
    ("engine",))
SERVE_STREAM_REPLICA_ITEMS = Counter(
    "ray_tpu_serve_stream_replica_items_total",
    "Tokens the replica's streams handed out (the items the handoff and "
    "store seconds are over)",
    ("engine",))
SERVE_STREAM_HANDOFFS = Counter(
    "ray_tpu_serve_stream_handoffs_total",
    "Landings (a tick's row, a prefill batch's first tokens) whose tokens "
    "the tick thread handed to the replica's streams: ONE call into the "
    "replica's event loop each, whatever the number of open streams; the "
    "replica's items over it are the tokens a call carried",
    ("engine",))
SERVE_STREAM_LOOP_SECONDS = Counter(
    "ray_tpu_serve_stream_loop_seconds_total",
    "Ingress seconds in a pull's two thread hops, neither of which waits "
    "for the engine: run_in_executor called -> the pull starts on a pool "
    "thread, and the pull returned -> the loop resumes",
    ("deployment",))
SERVE_STREAM_PULLS = Counter(
    "ray_tpu_serve_stream_pulls_total",
    "Pulls of streamed responses that brought items (one write each)",
    ("deployment",))
SERVE_STREAM_ITEMS = Counter(
    "ray_tpu_serve_stream_items_total",
    "Items streamed responses wrote; over the pulls it is the burst: 1.0 "
    "is token by token",
    ("deployment",))

# ------------------------------- disaggregated prefill/decode handoff (L6)
# The KV-block transfer plane between prefill and decode replicas: every
# cross-replica export/import rides the journal-gated helper in
# ray_tpu/serve/kv_transfer.py (a source lint pins the call sites), and
# these series are observed there. ``direction`` partitions the handoff
# wall into its three legs: export (arena gather -> host staging),
# channel (shm channel write->read, absent on the in-process fast path),
# import (crc verify + arena scatter + radix insert).
_KV_TRANSFER_TAGS = ("deployment", "direction")
SERVE_KV_TRANSFER_SECONDS = Histogram(
    "ray_tpu_serve_kv_transfer_seconds",
    "KV handoff leg wall time, by direction (export/channel/import)",
    boundaries=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0),
    tag_keys=_KV_TRANSFER_TAGS)
SERVE_KV_TRANSFER_BYTES = Counter(
    "ray_tpu_serve_kv_transfer_bytes_total",
    "Staging-buffer bytes moved by KV handoffs, by direction",
    _KV_TRANSFER_TAGS)
SERVE_KV_TRANSFER_BLOCKS = Counter(
    "ray_tpu_serve_kv_transfer_blocks_total",
    "Arena blocks moved by KV handoffs, by direction",
    _KV_TRANSFER_TAGS)
SERVE_HANDOFFS = Counter(
    "ray_tpu_serve_handoff_total",
    "Prefill->decode handoffs by outcome (ok: imported and streaming; "
    "prefill_died: death before the manifest — resubmitted, cause="
    "resubmit; decode_died: death after the journaled handoff — "
    "replayed as a fresh prefill, cause=resume; crc_mismatch: payload "
    "failed verification on import)",
    ("deployment", "outcome"))

# ------------------------------------------------ event/span buffer drops
EVENTS_DROPPED = Counter(
    "ray_tpu_events_dropped_total",
    "Task-event/span records shed by a full buffer, by buffer "
    "(timeline ring, per-channel BufferedPublisher, flight ring, GCS "
    "flight store) — a non-zero rate means traces/chains have holes",
    ("buffer",))
EVENTS_TOTAL = Counter(
    "ray_tpu_events_total",
    "Flight-recorder control-plane events emitted, by event type "
    "(lease transitions, drains, preemption notices, recoveries, chaos "
    "injections...); loss is counted in ray_tpu_events_dropped_total",
    ("type",))

# ---------------------------------------------------------------- train (L6)
TRAIN_REPORTS = Counter(
    "ray_tpu_train_reports_total",
    "train.report() rounds merged by the trainer",
    ("trainer",))
TRAIN_STEP_SECONDS = Histogram(
    "ray_tpu_train_step_seconds",
    "Wall time between consecutive merged report rounds",
    tag_keys=("trainer",))
TRAIN_TOKENS_PER_S = Gauge(
    "ray_tpu_train_tokens_per_s",
    "Training throughput as last reported by rank 0 (tokens_per_s key)",
    ("trainer",))
TRAIN_RESTARTS = Counter(
    "ray_tpu_train_restarts_total",
    "Elastic trainer restarts by failure cause (worker_lost/hang/"
    "preemption/resize/user) — fatal errors end the run and are not "
    "counted",
    ("trainer", "cause"))
TRAIN_WORLD_SIZE = Gauge(
    "ray_tpu_train_world_size",
    "Worker count the current training attempt was scheduled with "
    "(moves on elastic shrink/grow restarts)",
    ("trainer",))
TRAIN_RECOVERY_SECONDS = Histogram(
    "ray_tpu_train_recovery_seconds",
    "Failure detection to the restarted attempt's first report: group "
    "teardown + backoff + re-acquisition + mesh re-formation + manifest "
    "restore + first step",
    boundaries=(0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 180.0, 600.0,
                1800.0),
    tag_keys=("trainer",))
TRAIN_GOODPUT_SECONDS = Counter(
    "ray_tpu_train_goodput_seconds_total",
    "Attempt wall clock attributed by the goodput ledger, by component: "
    "step (productive: dispatching / free-running ahead of the device), "
    "input_stall (empty prefetch buffer), sync (windowed metric fetch), "
    "ckpt_block (checkpoint device->host snapshot), recovery (elastic "
    "recovery dead time + restore) — rank-0 ledger deltas plus the "
    "controller's inter-session recovery time",
    ("trainer", "component"))
TRAIN_GOODPUT_FRACTION = Gauge(
    "ray_tpu_train_goodput_fraction",
    "Fraction of the current attempt's wall clock per goodput-ledger "
    "component (components sum to 1; the dashboard stacks them)",
    ("trainer", "component"))
TRAIN_RANK_STEP_SECONDS = Histogram(
    "ray_tpu_train_rank_step_seconds",
    "Per-rank step wall time (dispatch->report gap recorded by each "
    "worker's session) — the controller's window merge of these feeds "
    "rank-skew scoring and straggler detection",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0,
                120.0),
    tag_keys=("trainer", "rank"))
TRAIN_STRAGGLER = Gauge(
    "ray_tpu_train_straggler",
    "1 while a rank is flagged as a straggler (mean step time over "
    "RAY_TPU_STRAGGLER_FACTOR x the window median for "
    "RAY_TPU_STRAGGLER_WINDOWS consecutive windows), 0 once cleared",
    ("trainer", "rank"))
TRAIN_INPUT_STALL = Histogram(
    "ray_tpu_train_input_stall_seconds",
    "Per-batch time the train loop sat blocked on an empty device-"
    "prefetch buffer (the input pipeline couldn't keep up) — the "
    "histogram _sum over wall time is the run's input-stall fraction",
    boundaries=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
                1.0, 5.0),
    tag_keys=("iterator",))
TRAIN_PREFETCH_OCCUPANCY = Gauge(
    "ray_tpu_train_prefetch_buffer_occupancy",
    "Device-prefetch buffer fill fraction (0 = consumer starved, "
    "1 = producer a full depth ahead) sampled at each put/get",
    ("iterator",))
TRAIN_INGEST_BYTES = Counter(
    "ray_tpu_train_ingest_bytes_total",
    "Host bytes staged onto the device mesh by the ingest prefetcher "
    "(decode output, pre-device_put) — its rate is the training "
    "data-plane bytes/s",
    ("iterator",))

# --------------------------------------------- continuous batching / LLM (L6)
CB_SLOT_OCCUPANCY = Gauge(
    "ray_tpu_cb_slot_occupancy",
    "Fraction of KV-cache slots active in the continuous-batching engine",
    ("engine",))
CB_ACTIVE_SLOTS = Gauge(
    "ray_tpu_cb_active_slots",
    "KV-cache slots currently decoding",
    ("engine",))
CB_WAITING_REQUESTS = Gauge(
    "ray_tpu_cb_waiting_requests",
    "Requests admitted but waiting for a free KV slot",
    ("engine",))
CB_DECODE_TOKENS = Counter(
    "ray_tpu_cb_decode_tokens_total",
    "Tokens produced by the continuous-batching decode loop",
    ("engine",))
CB_MOE_ASSIGNMENTS = Counter(
    "ray_tpu_cb_moe_assignments_total",
    "(token, expert) assignments the routed block computed in decode "
    "ticks, all layers (every slot routes, live or not)",
    ("engine",))
_SHARE_BOUNDS = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 0.99,
                 1.0)
CB_MOE_TOUCHED_SHARE = Histogram(
    "ray_tpu_cb_moe_experts_touched_share",
    "Per decode tick: the share of (layer, expert) pairs that got at "
    "least one row, so whose weights the tick read",
    boundaries=_SHARE_BOUNDS, tag_keys=("engine",))
CB_MOE_LOAD_IMBALANCE = Histogram(
    "ray_tpu_cb_moe_load_imbalance",
    "Per decode tick: the busiest expert's rows over the mean expert's, "
    "averaged over layers (1 = perfectly even)",
    boundaries=(1.0, 1.25, 1.5, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0, 16.0, 64.0),
    tag_keys=("engine",))
CB_MOE_LOCAL_ASSIGNMENTS = Counter(
    "ray_tpu_cb_moe_local_assignments_total",
    "Of ray_tpu_cb_moe_assignments_total, those that fell on an expert "
    "this chip holds (a model told which experts it holds; the others "
    "are another chip's part of the sum)",
    ("engine",))
CB_WINDOW_LIVE_BLOCK_SHARE = Histogram(
    "ray_tpu_cb_window_live_block_share",
    "Per decode tick: blocks a sliding-window layer's kernel visits (those "
    "that hold one of a query's last sliding_window keys) over the blocks "
    "a table of every position would have it visit",
    boundaries=_SHARE_BOUNDS, tag_keys=("engine",))
CB_LATENT_KV_BYTES = Gauge(
    "ray_tpu_cb_latent_kv_bytes",
    "Resident bytes of the latent cache of a model with latent-attention "
    "(MLA) layers: one padded row a token a layer, in the arena's place; "
    "fixed at construction",
    ("engine",))
CB_MLA_LIVE_TOKENS = Histogram(
    "ray_tpu_cb_mla_live_tokens",
    "Per decode tick of a model with latent-attention layers: the cache "
    "rows the tick's queries attended, summed over the live slots (each "
    "slot's position + 1); its sum over its count is the mean a tick",
    boundaries=[1e3, 1e4, 1e5, 2e5, 4e5, 6e5, 8e5, 1e6, 2e6],
    tag_keys=("engine",))
CB_WINDOW_KV_BYTES = Gauge(
    "ray_tpu_cb_window_kv_bytes",
    "Resident bytes of the sliding-window layers' per-slot rings; fixed "
    "at construction, whatever the contexts",
    ("engine",))
CB_FULL_KV_BYTES = Gauge(
    "ray_tpu_cb_full_kv_bytes",
    "Resident bytes of the arena of a model with sliding-window layers: "
    "the K/V of its full-attention layers alone",
    ("engine",))
CB_PAGED_LIVE_BLOCK_SHARE = Histogram(
    "ray_tpu_cb_paged_live_block_share",
    "Per decode tick: block-table entries that hold a key a query may "
    "see, over num_slots x max_blocks; the paged attention kernel "
    "visits those entries and no others",
    boundaries=_SHARE_BOUNDS, tag_keys=("engine",))
CB_PAGED_VISIT_FILL_SHARE = Histogram(
    "ray_tpu_cb_paged_visit_fill_share",
    "Per decode tick: blocks the paged attention kernel reads over the "
    "blocks its grid steps could hold (a step covers a few consecutive "
    "blocks of one slot, about 1 MB of K and V; a slot's last step may "
    "be short), over every attention layer",
    boundaries=_SHARE_BOUNDS, tag_keys=("engine",))
CB_TICK_OVERLAPPED = Counter(
    "ray_tpu_cb_tick_overlapped_total",
    "Decode ticks dispatched while another tick was still in flight: "
    "the device went into them without waiting for the host",
    ("engine",))
CB_TICK_MS = Histogram(
    "ray_tpu_cb_tick_ms",
    "Wall milliseconds per decode tick, one observation a tick: the "
    "time between two consecutive token rows reaching the host (from "
    "the dispatch where the device was idle or prefilling before it)",
    boundaries=(0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0,
                500.0, 1000.0),
    tag_keys=("engine",))
# The engine thread's time by phase (``tracing.phase`` in
# models/continuous_batching.py and the replica's tick loop): with
# per-tick sync the device idles while that thread is anywhere but inside
# a program's dispatch+fetch, so these sums split the chip's idle share
# by host cause. Each has a name of its own so a reader that sums label
# sets away can still tell them apart; the same intervals are
# ``engine.*`` annotations in a profiler trace (util/profile_gaps.py).
_STEP_MS_BOUNDS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0,
                   20.0, 50.0, 100.0, 500.0)
CB_STEP_LOCK_WAIT_MS = Histogram(
    "ray_tpu_cb_step_lock_wait_ms",
    "Milliseconds the replica's tick thread waited to get the engine "
    "lock back from submitting/cancelling callers, per tick-loop turn "
    "(span engine.lock_wait)",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_STEP_ADMIT_MS = Histogram(
    "ray_tpu_cb_step_admit_ms",
    "Host milliseconds of admission per step, the prefill programs "
    "taken out: queue scan, block allocation, prefix match, building "
    "the batch, first-token bookkeeping (span engine.admit)",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_PREFILL_CHUNK_MS = Histogram(
    "ray_tpu_cb_prefill_chunk_ms",
    "Milliseconds per prefill PROGRAM call (span engine.prefill.chunk): "
    "a prompt over the engine's prefill_chunk runs as several, back to "
    "back, and each one's first tokens land when it ends, so this is the "
    "time between two landings; the first call's clock starts where "
    "ray_tpu_cb_prefill_ms's does",
    boundaries=(1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                1000.0, 5000.0),
    tag_keys=("engine",))
CB_PREFILL_MS = Histogram(
    "ray_tpu_cb_prefill_ms",
    "Wall milliseconds per prefill BATCH: uploads, dispatch, compute, "
    "first-token fetch (span engine.prefill; the per-request "
    "ray_tpu_serve_request_prefill_seconds counts a batch once per row)",
    boundaries=(1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                1000.0, 5000.0),
    tag_keys=("engine",))
CB_STEP_UPLOAD_MS = Histogram(
    "ray_tpu_cb_step_upload_ms",
    "Host milliseconds re-uploading slot state (tokens, positions, step, "
    "block tables, limits) after membership changed (span engine.upload)",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_STEP_ACCOUNT_MS = Histogram(
    "ray_tpu_cb_step_account_ms",
    "Host milliseconds of the engine thread's own bookkeeping: slot/KV "
    "gauges, the speculation controller, the XLA monitor's "
    "note_execution with the live-byte estimate (span engine.account)",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_STEP_APPLY_MS = Histogram(
    "ray_tpu_cb_step_apply_ms",
    "Host milliseconds booking fetched tokens: finish detection, the "
    "landing's one hand-over to the replica's streams, the end-of-stream "
    "hand-over (span engine.apply)",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
# The same thread's timeline seen from the DEVICE: from
# the moment a landing leaves nothing queued on the device to the next
# dispatch, the device waits for this thread. One histogram a cause, one
# observation an interval, on ``time.perf_counter()`` like the tick's
# clock; with ``ray_tpu_cb_tick_ms`` and ``ray_tpu_cb_prefill_ms`` the
# four sums partition the thread's wall time.
CB_STARVED_AFTER_PREFILL_MS = Histogram(
    "ray_tpu_cb_starved_after_prefill_ms",
    "Device-empty milliseconds from a prefill's first tokens landing to "
    "the dispatch of the next decode tick: the slot-state uploads, the "
    "token merge and the dispatch itself",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_STARVED_TICK_LATE_MS = Histogram(
    "ray_tpu_cb_starved_tick_late_ms",
    "Device-empty milliseconds from a decode tick's row landing with "
    "nothing queued behind it to the dispatch of the next tick: the "
    "engine lock, booking, admission's host part, the interpreter lock",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_STARVED_BEFORE_PREFILL_MS = Histogram(
    "ray_tpu_cb_starved_before_prefill_ms",
    "Device-empty milliseconds up to the dispatch of a prefill's first "
    "program: waking, admission and building and uploading its arguments "
    "with nothing queued on the device",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
CB_IDLE_NO_WORK_MS = Histogram(
    "ray_tpu_cb_idle_no_work_ms",
    "Device-empty milliseconds with no live slot and nothing waiting, up "
    "to the next request's arrival: not starved, booked so that the "
    "thread's timeline adds up",
    boundaries=_STEP_MS_BOUNDS, tag_keys=("engine",))
# The same timeline seen from a LIVE SLOT (a request past its first
# token): every millisecond of it is one or the other.
CB_SLOT_ADVANCING_MS = Counter(
    "ray_tpu_cb_slot_advancing_ms_total",
    "Slot-milliseconds inside a decode tick that advances the slot: each "
    "tick's ray_tpu_cb_tick_ms times its members",
    ("engine",))
CB_SLOT_STALLED_MS = Counter(
    "ray_tpu_cb_slot_stalled_ms_total",
    "Slot-milliseconds a live slot stood still: each prefill batch's "
    "ray_tpu_cb_prefill_ms times the slots live before it, and each "
    "device-starved interval times the slots live in it",
    ("engine",))
# A third part since the gathered admission: a slot a hold keeps empty is
# neither (``ContinuousBatcher._holds_admission``).
CB_ADMIT_HELD_SLOT_MS = Counter(
    "ray_tpu_cb_admit_held_slot_ms_total",
    "Slot-milliseconds a free slot stood empty because the engine held "
    "its admission back for the slots that free next to join the same "
    "prefill batch: each tick's ray_tpu_cb_tick_ms times the slots the "
    "hold kept empty through it",
    ("engine",))
CB_ADMIT_HELD_TICKS = Counter(
    "ray_tpu_cb_admit_held_ticks_total",
    "Decode ticks that ran while a held admission kept free slots empty",
    ("engine",))
CB_PREFILL_PADDED_ROWS = Counter(
    "ray_tpu_cb_prefill_padded_rows_total",
    "Rows the prefill programs ran, a batch counted once: the batch's "
    "requests padded to a power of two (beside "
    "ray_tpu_cb_prefill_requests_total, the real rows)",
    ("engine",))
CB_PREFILL_PADDED_TOKENS = Counter(
    "ray_tpu_cb_prefill_padded_tokens_total",
    "Token positions the prefill programs ran: padded rows x padded "
    "length x chunks (beside ray_tpu_cb_prefill_tokens_total, the real "
    "tokens)",
    ("engine",))
CB_PREFILL_REQUESTS = Counter(
    "ray_tpu_cb_prefill_requests_total",
    "Requests admitted into KV slots via (batched bucketed) prefill",
    ("engine",))
CB_PREFILL_TOKENS = Counter(
    "ray_tpu_cb_prefill_tokens_total",
    "Prompt tokens prefilled (true lengths; bucket padding excluded)",
    ("engine",))
CB_KV_BLOCKS_USED = Gauge(
    "ray_tpu_cb_kv_blocks_used",
    "Paged-KV arena blocks currently reserved by active slots",
    ("engine",))
CB_KV_BLOCKS_TOTAL = Gauge(
    "ray_tpu_cb_kv_blocks_total",
    "Paged-KV arena capacity in blocks (garbage block excluded)",
    ("engine",))
CB_KV_FRAG_RATIO = Gauge(
    "ray_tpu_cb_kv_frag_ratio",
    "Reserved-but-unwritten fraction of used paged-KV blocks "
    "(internal fragmentation of the arena)",
    ("engine",))
CB_PREFIX_HIT_TOKENS = Counter(
    "ray_tpu_cb_prefix_hit_tokens_total",
    "Prompt tokens served from cached prefix blocks instead of being "
    "prefilled (radix prefix cache hits, block-aligned)",
    ("engine",))
CB_PREFIX_MISS_TOKENS = Counter(
    "ray_tpu_cb_prefix_miss_tokens_total",
    "Prompt tokens actually prefilled (novel suffixes; the whole prompt "
    "on a cold miss) — hit/(hit+miss) is the prefix hit rate",
    ("engine",))
CB_KV_BLOCKS_CACHED = Gauge(
    "ray_tpu_cb_kv_blocks_cached",
    "Refcount-0 prefix blocks parked in the radix LRU: revivable by a "
    "prefix match, reclaimed before admission blocks on the arena",
    ("engine",))
CB_KV_BLOCKS_SHARED = Gauge(
    "ray_tpu_cb_kv_blocks_shared",
    "Indexed prefix blocks pinned (refcounted) by at least one live "
    "slot — never reclaimed while referenced",
    ("engine",))
CB_SPEC_DRAFT_TOKENS = Counter(
    "ray_tpu_cb_spec_draft_tokens_total",
    "Tokens proposed by the speculative-decode drafter (k per slot per "
    "spec tick); with accepted_tokens this prices how much verify "
    "bandwidth the drafts are buying",
    ("engine",))
CB_SPEC_ACCEPTED_TOKENS = Counter(
    "ray_tpu_cb_spec_accepted_tokens_total",
    "Drafted tokens the batched verify pass accepted (committed beyond "
    "the one token a plain tick would have produced)",
    ("engine",))
CB_SPEC_ACCEPT_RATE = Gauge(
    "ray_tpu_cb_spec_accept_rate",
    "Windowed speculative-decode accept rate (accepted/drafted over the "
    "last RAY_TPU_SPEC_WINDOW spec ticks) — the controller input that "
    "moves spec_k along its rung ladder",
    ("engine",))
CB_STATE_CACHE_BYTES = Gauge(
    "ray_tpu_cb_state_cache_bytes",
    "Resident bytes of the per-slot state cache beside the K/V arena "
    "(Mamba-2 or Gated DeltaNet layers: recurrent state and convolution "
    "tail of every slot); fixed at construction, whatever the contexts",
    ("engine",))
CB_STATE_LIVE_SLOTS = Histogram(
    "ray_tpu_cb_state_live_slots",
    "Per decode tick of a model with recurrent layers: the slots that "
    "held a live request, whose states the tick HAD to advance (the "
    "kernels advance every slot's); its sum over its count is the mean "
    "a tick",
    boundaries=[1, 2, 4, 8, 16, 32, 48, 64, 96, 128, 192, 256, 512],
    tag_keys=("engine",))
CB_PREFILL_STATE_CARRIES = Counter(
    "ray_tpu_cb_prefill_state_carries_total",
    "Prefill chunks of real prompts (a request's chunk counted once, "
    "padding rows not at all) that started from the recurrent state and "
    "conv tail the chunk before them installed in the slot's row of the "
    "state cache: every chunk of a linear-attention model's prompt but "
    "its first (beside ray_tpu_cb_state_installs_total, the prompts)",
    ("engine",))
CB_LOOP_ROWS = Counter(
    "ray_tpu_cb_loop_rows_total",
    "Live rows of the decode ticks of a model with a looped layer stack "
    "(loop_steps > 1; models/looped.py): one a decoded token, the overrun "
    "row of a request that had just ended included. No series exists for "
    "a model without a loop",
    ("engine",))
CB_LOOP_STEPS = Counter(
    "ray_tpu_cb_loop_steps_total",
    "Passes of the layer stack the decode ticks of a looped model ran, "
    "summed over their live rows: rows x steps, the steps read off the "
    "gates the tick's row carries (beside ray_tpu_cb_loop_rows_total: "
    "their ratio is loop_steps, or the program left work out)",
    ("engine",))
CB_LOOP_KV_BYTES = Gauge(
    "ray_tpu_cb_loop_kv_bytes",
    "Bytes of a looped model's K/V arena, loop_steps x num_layers rows a "
    "token: fixed at construction",
    ("engine",))
CB_EVA_WINDOWS_CLOSED = Counter(
    "ray_tpu_cb_eva_windows_closed_total",
    "Windows of an EVA-attention model's contexts that filled and were "
    "pooled into their summaries, by the program that filled them: a "
    "prefill chunk (a whole window of a prompt: its raw keys never reach "
    "the arena) or a decode tick (the window's blocks rewritten in "
    "place, inside the tick)",
    ("engine", "phase"))
CB_EVA_BLOCKS_RETIRED = Counter(
    "ray_tpu_cb_eva_blocks_retired_total",
    "Arena blocks that went back to the allocator because a decode tick "
    "closed their window (all of the window's but its summaries'; a "
    "prefill never holds them), in the step that dispatched that tick",
    ("engine",))
CB_EVA_SUMMARY_KEYS = Histogram(
    "ray_tpu_cb_eva_summary_keys",
    "Per decode tick of an EVA-attention model: the summaries of closed "
    "windows its queries attended, summed over the live slots, a layer "
    "(beside ray_tpu_cb_eva_window_keys: the two are a tick's attended "
    "keys; two series because a label's values are summed where the "
    "benchmark reads the registry)",
    boundaries=[64, 256, 1024, 4096, 8192, 16384, 32768, 65536, 131072],
    tag_keys=("engine",))
CB_EVA_WINDOW_KEYS = Histogram(
    "ray_tpu_cb_eva_window_keys",
    "Per decode tick of an EVA-attention model: the raw keys of the open "
    "windows its queries attended (each query's own included), summed "
    "over the live slots, a layer",
    boundaries=[64, 256, 1024, 4096, 8192, 16384, 32768, 65536, 131072],
    tag_keys=("engine",))
CB_EVA_CACHE_BYTES = Gauge(
    "ray_tpu_cb_eva_cache_bytes",
    "Bytes of the arena blocks the live slots of an EVA-attention model "
    "hold now: their closed windows' summaries and their open windows as "
    "far as they are filled",
    ("engine",))
CB_EVA_UNCOMPRESSED_BYTES = Gauge(
    "ray_tpu_cb_eva_uncompressed_bytes",
    "Bytes of the blocks the same live contexts would hold with every "
    "key kept (what ray_tpu_cb_eva_cache_bytes is a share of)",
    ("engine",))
CB_CCA_KV_BYTES = Gauge(
    "ray_tpu_cb_cca_kv_bytes",
    "Resident bytes of the K/V arena of a model with CCA layers "
    "(layers x blocks x block x 2 planes x KV heads x head size x "
    "itemsize): keys and values in the compressed latent",
    ("engine",))
CB_CCA_TAIL_BYTES = Gauge(
    "ray_tpu_cb_cca_tail_bytes",
    "Resident bytes of the tail cache a model with CCA layers keeps "
    "beside the arena: one row [u | a | v2] a slot a layer (the two "
    "convolutions' last inputs and the next token's shifted value half); "
    "fixed at construction, whatever the contexts",
    ("engine",))
CB_STATE_INSTALLS = Counter(
    "ray_tpu_cb_state_installs_total",
    "Prompts whose final recurrent state a prefill installed in a slot "
    "of the state cache",
    ("engine",))
CB_SPEC_K = Gauge(
    "ray_tpu_cb_spec_k",
    "Live speculative draft depth k the engine is dispatching (0 = the "
    "controller parked on the plain tick; configured maximum is the "
    "spec_k knob)",
    ("engine",))

# ------------------------------------------------- XLA plane (_private/
# xla_monitor.py): compiles/retraces per instrumented program, compiler
# cost analysis, and achieved throughput against it.
XLA_COMPILES = Counter(
    "ray_tpu_xla_compiles_total",
    "XLA compilations of instrumented programs (one per new signature)",
    ("program",))
XLA_COMPILE_SECONDS = Histogram(
    "ray_tpu_xla_compile_seconds",
    "Wall time of one XLA compilation (lower + compile)",
    boundaries=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0),
    tag_keys=("program",))
XLA_RETRACES = Counter(
    "ray_tpu_xla_retraces_total",
    "Recompiles of an instrumented program for an UNEXPECTED new "
    "shape/dtype signature (bucketed growth is exempt); the offending "
    "signature diff is logged",
    ("program",))
XLA_PROGRAM_FLOPS = Gauge(
    "ray_tpu_xla_program_flops",
    "Compiler cost-analysis FLOPs per invocation of the latest "
    "compiled signature",
    ("program",))
XLA_PROGRAM_BYTES = Gauge(
    "ray_tpu_xla_program_bytes_accessed",
    "Compiler cost-analysis bytes accessed (HBM traffic) per invocation "
    "of the latest compiled signature",
    ("program",))
XLA_ACHIEVED_FLOPS = Gauge(
    "ray_tpu_xla_achieved_flops_per_s",
    "Achieved FLOP/s: cost-analysis FLOPs over measured step/tick wall "
    "time (no estimation)",
    ("program",))
XLA_ACHIEVED_BW = Gauge(
    "ray_tpu_xla_achieved_bandwidth_bytes_per_s",
    "Achieved memory bandwidth: cost-analysis bytes accessed over "
    "measured step/tick wall time",
    ("program",))
XLA_MFU = Gauge(
    "ray_tpu_xla_model_flops_utilization",
    "Achieved FLOP/s over the chip's peak (emitted only on known "
    "device kinds)",
    ("program",))
# The call record (``xla_monitor._CallRecord``): one record a measured
# execution, fed by ``note_execution``; a stalled stretch books the last
# two when it closes.
XLA_RESULTS_READY = Counter(
    "ray_tpu_xla_results_ready_at_fetch_total",
    "Measured executions whose whole result answered is_ready() when "
    "the host came to fetch it: the host was the slower side of that "
    "call",
    ("program",))
XLA_FETCH_WAIT_SECONDS = Counter(
    "ray_tpu_xla_fetch_wait_seconds_total",
    "Seconds the host blocked in the fetch of measured executions "
    "(the device, its runtime or the transfer back was the slower side)",
    ("program",))
XLA_STALL_STRETCHES = Counter(
    "ray_tpu_xla_stall_stretches_total",
    "Stalled stretches closed: runs of measured executions several "
    "times over their shape's own median; side = host (results were "
    "ready before the host came), device (the host waited), mixed",
    ("side",))
XLA_STALL_EXCESS_SECONDS = Counter(
    "ray_tpu_xla_stall_excess_seconds_total",
    "Seconds closed stalled stretches cost: each slow call's wall time "
    "less its shape's median; side as on the stretches' counter",
    ("side",))

# --------------------------------------------- device memory vitals
DEVICE_MEM_USED = Gauge(
    "ray_tpu_device_mem_used_bytes",
    "Accelerator bytes_in_use from device memory_stats() (absent on "
    "backends without memory stats, e.g. CPU)",
    ("node_id", "device"))
DEVICE_MEM_PEAK = Gauge(
    "ray_tpu_device_mem_peak_bytes",
    "Accelerator peak_bytes_in_use from device memory_stats()",
    ("node_id", "device"))
DEVICE_MEM_LIMIT = Gauge(
    "ray_tpu_device_mem_limit_bytes",
    "Accelerator bytes_limit from device memory_stats()",
    ("node_id", "device"))

# --------------------------------------------- checkpoint plane (ckpt/)
CKPT_BLOCK_MS = Histogram(
    "ray_tpu_ckpt_block_ms",
    "Milliseconds the step loop was blocked by a save (device→host "
    "snapshot only; serialization and the write run in the background)",
    boundaries=(1.0, 5.0, 10.0, 50.0, 100.0, 500.0, 1000.0, 5000.0,
                30000.0),
    tag_keys=("run",))
CKPT_SAVE_SECONDS = Histogram(
    "ray_tpu_ckpt_save_seconds",
    "End-to-end wall time of one participant's checkpoint persist "
    "(snapshot through shard write and commit attempt)",
    boundaries=(0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0),
    tag_keys=("run",))
CKPT_RESTORE_SECONDS = Histogram(
    "ray_tpu_ckpt_restore_seconds",
    "Wall time of one elastic restore (manifest read, shard reassembly, "
    "re-shard device_put)",
    boundaries=(0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0),
    tag_keys=("run",))
CKPT_BYTES = Counter(
    "ray_tpu_ckpt_bytes_total",
    "Checkpoint bytes moved by this process, by direction (save/restore)",
    ("run", "direction"))
CKPT_SAVES = Counter(
    "ray_tpu_ckpt_saves_total",
    "Checkpoint persists by outcome: committed (this participant flipped "
    "the manifest), registered (a peer commits), failed",
    ("run", "outcome"))
CKPT_PREEMPT_NOTICES = Counter(
    "ray_tpu_ckpt_preempt_notices_total",
    "Preemption notices delivered to this process, by source "
    "(local/publish/pubsub)",
    ("source",))

# --------------------------------------------- RL weight-sync plane (rl/)
RL_SYNC_SECONDS = Histogram(
    "ray_tpu_rl_weight_sync_seconds",
    "Wall time of one weight-sync hop, by path (publish: trainer manifest "
    "build + checkpoint persist + channel write; subscribe: channel read + "
    "crc verify + reshard; fallback: checkpoint-plane restore when the "
    "fast path is unavailable)",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    tag_keys=("run", "path"))
RL_SYNC_BYTES = Counter(
    "ray_tpu_rl_weight_sync_bytes_total",
    "Weight bytes moved by the sync plane, by path "
    "(publish/subscribe/fallback)",
    ("run", "path"))
RL_VERSION = Gauge(
    "ray_tpu_rl_weight_sync_version",
    "Latest weight version seen, by role (trainer: last published; "
    "generator: version live in the serving engine) — the trainer/"
    "generator gap is the sync lag in versions",
    ("run", "role"))
RL_ROLLOUT_STALENESS = Gauge(
    "ray_tpu_rl_rollout_staleness",
    "Worst sequence staleness (trainer version minus producing weight "
    "version) in the most recent generation phase",
    ("run",))
RL_SWAPS = Counter(
    "ray_tpu_rl_weight_swaps_total",
    "Generator weight swaps applied at a tick boundary, by cause "
    "(publish/fallback/restore)",
    ("run", "cause"))
RL_SYNC_SHED = Counter(
    "ray_tpu_rl_weight_sync_shed_total",
    "Published versions a lagging subscriber never acked before the "
    "writer overwrote them (shed-with-attribution: the subscriber tag "
    "names the laggard; it re-converges via the checkpoint fallback)",
    ("run", "subscriber"))

# --------------------------------------- autoscaler reconciler (L7)
AUTOSCALER_ALLOC_FAILURES = Counter(
    "ray_tpu_autoscaler_allocation_failures_total",
    "Provider create_node failures observed by the reconciler "
    "(quota/stockout); a streak opens the exponential launch backoff",
    ("provider",))
AUTOSCALER_TICK_FAILURES = Gauge(
    "ray_tpu_autoscaler_consecutive_tick_failures",
    "Consecutive reconcile ticks that raised (0 = healthy); a streak "
    "backs off the tick interval and the last error is surfaced in "
    "Autoscaler.summary() and the dashboard",
    ("provider",))

# --------------------------------------- chip pool arbiter (L7, arbiter.py)
# The serve<->train chip-handoff plane: every chip sits in exactly one
# ledger state (serve / train / in_flight), and every lease transition is
# journaled into the __pool__ KV so an arbiter restart resumes (or rolls
# back) handoffs mid-flight.
POOL_CHIPS = Gauge(
    "ray_tpu_pool_chips",
    "Chips per ledger owner (serve / train / in_flight) — the three "
    "always sum to the pool total (the conservation invariant)",
    ("owner",))
POOL_LEASES = Gauge(
    "ray_tpu_pool_leases",
    "Live (non-terminal) chip leases by state-machine stage",
    ("stage",))
POOL_HANDOFFS = Counter(
    "ray_tpu_pool_handoffs_total",
    "Chip handoffs reaching a terminal disposition, by direction "
    "(serve_to_train/train_to_serve) and outcome (committed: recipient "
    "confirmed and the lease went live; returned: lease deadline lapsed "
    "or an SLO reversal gave the chips back; aborted: rolled back before "
    "commit)",
    ("direction", "outcome"))
POOL_HANDOFF_SECONDS = Histogram(
    "ray_tpu_pool_handoff_seconds",
    "Wall time from lease creation to COMMITTED (donor drain/shrink + "
    "recipient absorb + confirmation), by direction",
    boundaries=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 300.0,
                1800.0),
    tag_keys=("direction",))
POOL_SLO_REVERSALS = Counter(
    "ray_tpu_pool_slo_reversals_total",
    "SLO-guard interventions: a planned take of serve chips refused "
    "(refused) or a committed serve->train lease reversed (reversed), "
    "by the breaching signal (shed_rate/ttft_p95/latency_p95)",
    ("action", "signal"))
POOL_INVARIANT_VIOLATIONS = Counter(
    "ray_tpu_pool_invariant_violations_total",
    "Chip-conservation invariant violations detected by the ledger "
    "verifier (a chip in two ledger states, or orphaned) — any nonzero "
    "value is a bug",
    ("kind",))

# ---------------------------------------------------- shared readbacks
def serve_shed_total(deployment: str) -> float:
    """Cumulative ingress sheds for one deployment (every
    ``shed_*``-tagged outcome) — the single definition the serve
    autoscaler's shed signal and the chip-pool SLO guard both read, so
    a new shed outcome tag cannot silently diverge the two."""
    total = 0.0
    for _name, key, value in SERVE_REQ_OUTCOMES.samples():
        tags = dict(key)
        if tags.get("deployment") == deployment and \
                str(tags.get("outcome", "")).startswith("shed"):
            total += value
    return total


# --------------------------------------------- on-demand profiler capture
PROFILE_CAPTURES = Counter(
    "ray_tpu_profile_captures_total",
    "jax.profiler trace captures executed by this process, by outcome",
    ("status",))

# ------------------------------------- GCS head / control plane (L1 GCS)
# Every global concern terminates on the head process; these series are
# the measurement substrate for ROADMAP item 5 (head scale-out). The KV
# namespace tag is bounded: reserved ``__*__`` namespaces keep their own
# label, everything else folds into ``user``.
GCS_KV_OPS = Counter(
    "ray_tpu_gcs_kv_ops_total",
    "GCS KV handler calls by operation (put/get/del/keys) and namespace "
    "(reserved __*__ namespaces; all user namespaces fold into 'user')",
    ("op", "namespace"))
GCS_KV_BYTES = Counter(
    "ray_tpu_gcs_kv_bytes_total",
    "GCS KV payload bytes moved by operation and namespace (put = value "
    "bytes written, get = value bytes returned, del = value bytes "
    "released) — exact by construction, asserted by tier-1",
    ("op", "namespace"))
GCS_PUBSUB_PUBLISHED = Counter(
    "ray_tpu_gcs_pubsub_published_total",
    "Messages accepted by the head pubsub plane, per channel",
    ("channel",))
GCS_PUBSUB_FANOUT_SECONDS = Histogram(
    "ray_tpu_gcs_pubsub_fanout_seconds",
    "Publish -> subscriber-stream-delivery latency per channel (stamped "
    "at enqueue inside Publish, observed when Subscribe yields the "
    "message)",
    boundaries=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
    tag_keys=("channel",))
GCS_PUBSUB_QUEUE_DEPTH = Gauge(
    "ray_tpu_gcs_pubsub_queue_depth",
    "Deepest per-subscriber delivery queue per channel, sampled at "
    "publish time (a growing depth names the slow consumer's channel)",
    ("channel",))
GCS_PUBSUB_DROPPED = Counter(
    "ray_tpu_gcs_pubsub_dropped_total",
    "Messages dropped for one slow subscriber whose delivery queue hit "
    "RAY_TPU_PUBSUB_QUEUE_MAX, attributed to that subscriber id",
    ("channel", "subscriber"))
GCS_WAL_QUEUE_DEPTH = Gauge(
    "ray_tpu_gcs_wal_queue_depth",
    "Records buffered in the WAL append queue awaiting the writer "
    "thread, by backend class",
    ("backend",))
GCS_WAL_WATERMARK_LAG = Gauge(
    "ray_tpu_gcs_wal_watermark_lag",
    "WAL queued-vs-durable sequence gap (records accepted but not yet "
    "fsynced) — sustained growth means the drain cannot keep up",
    ("backend",))
GCS_WAL_FSYNC_SECONDS = Histogram(
    "ray_tpu_gcs_wal_fsync_seconds",
    "Wall time of one WAL drain batch write+fsync, by backend class",
    boundaries=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5),
    tag_keys=("backend",))
GCS_WAL_COMPACTION_SECONDS = Histogram(
    "ray_tpu_gcs_wal_compaction_seconds",
    "Wall time of one WAL snapshot compaction (install_snapshot), by "
    "backend class",
    boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0),
    tag_keys=("backend",))
GCS_WAL_SYNC_TIMEOUTS = Counter(
    "ray_tpu_gcs_wal_sync_timeouts_total",
    "WriteAheadLog.sync() calls that timed out before the durable "
    "watermark caught up (callers that ignore the bool still get "
    "counted here)",
    ("backend",))
GCS_HEALTH_TICK_SECONDS = Histogram(
    "ray_tpu_gcs_health_tick_seconds",
    "Wall time of one GCS health-loop tick (lapse scan + probe "
    "scheduling + periodic reconcile/sweep work riding the tick)",
    boundaries=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
    tag_keys=("role",))
GCS_HEALTH_PROBE_BACKLOG = Gauge(
    "ray_tpu_gcs_health_probe_backlog",
    "Nodes with lapsed heartbeats pending a liveness probe, sampled "
    "each health tick",
    ("role",))

# --------------------------------------- RPC saturation + client retries
RPC_QUEUE_WAIT_SECONDS = Histogram(
    "ray_tpu_rpc_queue_wait_seconds",
    "Server-side request wait from executor enqueue to handler start, "
    "per service — the saturation signal: diverges when the gRPC "
    "thread pool is full",
    boundaries=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0),
    tag_keys=("service",))
RPC_EXECUTOR_OCCUPANCY = Gauge(
    "ray_tpu_rpc_executor_occupancy",
    "Fraction of the service's gRPC thread pool currently running "
    "handlers (1.0 = saturated; new requests queue)",
    ("service",))
RPC_ACTIVE_STREAMS = Gauge(
    "ray_tpu_rpc_active_streams",
    "Live server-streaming RPCs per service/method (Subscribe streams "
    "hold a pool thread for their whole life)",
    ("service", "method"))
RPC_CLIENT_RETRIES = Counter(
    "ray_tpu_rpc_client_retries_total",
    "Client-stub retry attempts by service, method, and gRPC status "
    "reason (an UNAVAILABLE storm against a restarting head shows up "
    "here instead of as silent backoff)",
    ("service", "method", "reason"))
