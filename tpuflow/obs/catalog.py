"""Canonical catalog of telemetry names.

Every span/counter/gauge/histogram/event name emitted anywhere in tpuflow
is registered here, once, with its kind and meaning. The catalog is the
contract between emitters (runner/trainer/ckpt/data/infer) and consumers
(the timeline card, ``obs.summarize``, downstream flows reading a run's
telemetry): names can't silently drift between the two sides because
``tools/obs_lint.py`` (and its pytest twin) greps every literal emitter
call in the tree and fails on any name missing from this table.

Kinds:

- ``span``      — a timed region: one event with ``ts`` (wall-clock start)
                  and ``dur_s`` (monotonic duration).
- ``counter``   — a monotonically accumulated amount (sum over the run).
- ``gauge``     — a sampled instantaneous value (last/max are meaningful,
                  sums are not).
- ``histogram`` — raw observations; consumers compute count/mean/p50/max.
- ``event``     — a point-in-time record (warnings, markers, reports).
"""

from __future__ import annotations

CATALOG: dict[str, tuple[str, str]] = {
    # ---------------------------------------------------------------- flow
    "flow.run": ("span", "one whole flow run, start → terminal status"),
    "flow.step": ("span", "one step/task execution (attempt granularity)"),
    "flow.gang": ("span", "gang execution: members launched → all joined"),
    "flow.gang_member": ("span", "one gang member process's step body"),
    "flow.retry": ("counter", "step attempts that failed and were retried"),
    "flow.retry_backoff_s": (
        "gauge",
        "jittered exponential backoff slept before a retry attempt",
    ),
    "flow.member_failed": (
        "event",
        "gang supervisor: first non-zero member exit (member, rc, log "
        "tail); surviving peers are killed promptly",
    ),
    "flow.heartbeat_stall": (
        "event",
        "gang supervisor: a member's heartbeat went silent past the stall "
        "timeout (member, age_s, last_step — heartbeats stamp the current "
        "step, so the report says WHERE the member stalled, not just how "
        "long ago); the gang is killed",
    ),
    "flow.preempt": (
        "event",
        "a gang member exited with the requeue code after a preemption "
        "drain; the step reruns without consuming the retry budget",
    ),
    # Elastic gang (ISSUE 7): member loss becomes a mesh resize at
    # step-fence granularity instead of a requeue-the-world.
    "flow.member_lost": (
        "event",
        "elastic supervisor: a gang member died (member, rc, log tail, "
        "flight, survivor count) and the gang SHRINKS over the survivors "
        "instead of failing the step — contrast flow.member_failed, the "
        "fail-fast path",
    ),
    "flow.gang_resize": (
        "span",
        "one mesh re-form, announce → all survivors joined the new "
        "generation (generation, kind=shrink|grow, from/to member "
        "counts); feeds the goodput ledger's `resize` bucket",
    ),
    "flow.card_render": ("span", "card HTML render at step completion"),
    # --------------------------------------------------------------- train
    "train.fit": ("span", "Trainer.fit: mesh build + worker loop + drain"),
    "train.epoch": ("span", "one training epoch (steady-state steps only)"),
    "train.compile": ("span", "first-step jit trace + compile, fenced"),
    "train.step_s": ("histogram", "steady-state per-step wall time, fenced"),
    "train.validation": ("span", "held-out validation pass"),
    "train.tokens": ("counter", "steady-state tokens consumed by train steps"),
    "train.report": ("event", "one TrainContext.report: step + metrics"),
    "train.dispatch_depth": (
        "gauge",
        "dispatch-ahead window depth the hot loop resolved "
        "(TPUFLOW_DISPATCH_DEPTH): how many steps may be in flight "
        "before the host settles the oldest step's scalars",
    ),
    # Raise-MFU step work (ISSUE 10): remat-selector provenance and the
    # comm/compute roofline attribution pair.
    "train.remat_policy": (
        "event",
        "resolved remat selector for the leg (none|full|dots|policy "
        "name; TPUFLOW_REMAT_POLICY beats the config), `saves`, the named "
        "values a rematerialised block keeps beside its input (the "
        "attention kernels' flash_out and flash_lse under full and dots, "
        "empty otherwise), plus whether the "
        "comm-overlapped accumulation scan is armed — the run's "
        "memory/recompute/overlap trade, auditable from the stream",
    ),
    "train.exposed_comm_s": (
        "gauge",
        "per-step seconds NOT at peak compute (mean epoch step wall − "
        "6·N·tokens/peak): an UPPER bound on exposed communication — "
        "memory stalls and bubbles charge here too, keeping the overlap "
        "claim conservative (train.step.comm_attribution; TPU only)",
    ),
    "train.comm_overlap_s": (
        "gauge",
        "per-step seconds of FSDP collective time hidden behind compute "
        "(comm roofline − exposed_comm_s, floored at 0): a LOWER bound "
        "on overlapped comm (train.step.comm_attribution; TPU only)",
    ),
    # ---------------------------------------------------------------- ckpt
    "ckpt.save": ("span", "checkpoint save, save() → commit; bytes + gbps"),
    "ckpt.restore": ("span", "checkpoint restore; bytes + gbps when known"),
    "ckpt.verify": (
        "event",
        "explicit integrity audit of one step (verify_step): shard count "
        "checked + outcome",
    ),
    "ckpt.corrupt": (
        "event",
        "a shard failed crc32/truncation verification; restore fell back "
        "to the next tier / previous committed step or raised — never "
        "silent",
    ),
    # Durable checkpointing under storage failure (ISSUE 5): retrying I/O,
    # staged atomic commits + GC, the local fast tier, emergency saves.
    "ckpt.io_retry": (
        "event",
        "one transient storage error absorbed by the retrying I/O wrapper "
        "(op, path, attempt, jittered backoff slept)",
    ),
    "ckpt.io_error": (
        "event",
        "a storage operation failed for good: permanent errno or retry "
        "budget exhausted (raises CheckpointIOError)",
    ),
    "ckpt.save_failed": (
        "event",
        "one step's save died on a classified storage error after "
        "retries: staging reclaimed, history entry dropped, training "
        "continues on the previous committed step — never a member death",
    ),
    "ckpt.gc": (
        "event",
        "manager startup reclaimed killed-writer leftovers: staged .tmp "
        "dirs, uncommitted step dirs, stale local-tier staging/overflow",
    ),
    "ckpt.upload": (
        "span",
        "local fast tier → persistent run dir copy of one committed step "
        "(async saver thread); ok=False means the step is durable locally "
        "only",
    ),
    "ckpt.restore_tier": (
        "event",
        "which tier served a restore (local | persistent) for which step "
        "— the fallback-ladder evidence trail",
    ),
    "ckpt.emergency_save": (
        "event",
        "last-chance synchronous commit on the fastest tier inside a "
        "closing preemption-grace window (upload skipped); the requeued "
        "attempt resumes from this step",
    ),
    # ---------------------------------------------------------------- data
    "data.batch_wait_s": ("histogram", "time the consumer blocked per batch"),
    "data.prefetch_hit": ("counter", "batches ready with no consumer wait"),
    "data.prefetch_miss": ("counter", "batches the consumer had to wait for"),
    "data.host_wait_s": (
        "gauge",
        "seconds the consuming loop actually blocked for this batch "
        "(~0 on every prefetch hit = the input pipeline ran entirely "
        "behind device compute)",
    ),
    "data.wait": (
        "span",
        "the consuming loop blocked for one batch (hit = the batch was "
        "already queued); the span form of data.host_wait_s, on the "
        "profiler's host plane in a traced run",
    ),
    # ------------------------------------------------------------- set-up
    # Where launch-to-first-step goes (ISSUE 26): one span per backend
    # compile-or-load from JAX's own monitoring events, and the state's
    # initializer.
    "compile": (
        "span",
        "one backend compile of a jitted program, or its load from the "
        "persistent cache (cache_hit true/false, null where the cache "
        "was not asked; program = the name JAX gives it)",
    ),
    "state.init": (
        "span",
        "create_sharded_state: trace, compile-or-load and dispatch of "
        "the born-sharded initializer (execution not awaited)",
    ),
    # --------------------------------------------------------------- infer
    "infer.predict": ("span", "BatchPredictor forward over one batch"),
    "infer.generate": ("span", "one generate() call; tokens + tokens/s"),
    "infer.generate_batch": ("span", "GenerationPredictor batch decode"),
    "infer.spec.forwards": ("counter", "speculative verify forwards"),
    "infer.spec.committed": ("counter", "tokens committed by speculation"),
    "infer.spec.acceptance": ("gauge", "realized tokens per verify forward"),
    # --------------------------------------------------------------- serve
    # Continuous-batching serving engine (ISSUE 8): request-level
    # telemetry from tpuflow.infer.serve, also mirrored live on the
    # /metrics exporter via the process ledger's serve_* snapshot keys.
    "serve.warmup": (
        "span",
        "AOT warm pass at server start: decode program + insert + every "
        "prefill bucket compiled-or-cache-loaded once (carries the jit "
        "cache sizes — the never-recompile baseline)",
    ),
    "serve.prefill": (
        "span",
        "one admission: chunked prefill of a request's prompt at its "
        "bucket width, fenced on the first generated token",
    ),
    "serve.decode": (
        "span",
        "one decode (spec: verify) block of the persistent slot-based "
        "program over one group's slots (quant = the int8 twin; "
        "decode_block tokens per live slot, one host sync); rows, pages = "
        "the block's operand shape; plus whatever the model sowed of its "
        "steps into its step_sum / step_max collections, summed / the "
        "largest over the block (a routed model: experts_touched, the "
        "distinct experts a live row chose over steps and routed layers; "
        "expert_max_load, the most tokens one expert got in a step over "
        "the mean) — ServeLedger's snapshot keeps both (step_sum, "
        "step_max, model_steps)",
    ),
    "serve.decode.dispatch": (
        "span",
        "child of serve.decode: host drafts and the enqueue of the "
        "block's program",
    ),
    "serve.decode.fence": (
        "span",
        "child of serve.decode: the host copy of the block's tokens, "
        "the one place the engine waits for the device",
    ),
    "serve.decode.merge": (
        "span",
        "child of serve.decode: the block's carries merged back into "
        "the host's slot state through the group mask",
    ),
    "serve.harvest": (
        "span",
        "a block's tokens handed to their requests, per-token latencies "
        "noted, ended requests' slots and pages freed",
    ),
    "serve.step": (
        "span",
        "one scheduler iteration, the root of the engine's spans: "
        "admissions, then one decode block per live group",
    ),
    "serve.admit": (
        "span",
        "one admission attempt of the head of the queue (request, "
        "admitted): page allocation and prefix lookup, with "
        "serve.prefill and serve.insert inside it. admitted=true adds "
        "slot, bucket, queue_wait_s, pages, shared_pages; an attempt "
        "the page pool refused (admitted=false, the request stays "
        "queued) carries only request and admitted",
    ),
    "serve.insert": (
        "span",
        "a prefill row (or restored pages) written into the cache for "
        "one request",
    ),
    "serve.first_token": (
        "event",
        "a request's first token exists (request; mono = the engine's "
        "own stamp): with serve.complete it bounds the interval in "
        "which the request decodes",
    ),
    "serve.complete": (
        "event",
        "a request finished (tokens, reason=eos|budget|capacity, "
        "ttft_s, decode_tokens_per_s)",
    ),
    "serve.queue_depth": (
        "gauge", "requests waiting for a free slot (sampled per iteration)"
    ),
    "serve.slot_occupancy": (
        "gauge", "live fraction of the engine's fixed decode slots"
    ),
    "serve.ttft_s": (
        "gauge",
        "one request's submit → first-token latency (queue wait + "
        "bucketed prefill)",
    ),
    "serve.tokens_per_s": (
        "gauge",
        "one completed request's post-first-token decode rate (its slot's "
        "share of the batched decode program)",
    ),
    "serve.tokens": ("counter", "generated tokens served by the engine"),
    "serve.requests": ("counter", "requests completed by the engine"),
    # Paged KV serving (ISSUE 11): page-pool headroom, shared-prefix
    # reuse, speculative acceptance, and the eviction evidence trail —
    # the gauges the Serving runbook's paged section reads, mirrored as
    # tpuflow_serve_* names on /metrics.
    "serve.pages_free": (
        "gauge",
        "KV pages allocatable right now (truly free + idle prefix-cache "
        "pages reclaimable by eviction); admission blocks — queues, "
        "never drops — when a request's page need exceeds this",
    ),
    "serve.prefix_hits": (
        "gauge",
        "cumulative prompt pages served from the shared-prefix cache "
        "instead of being allocated + recomputed into a private copy "
        "(refcounted page reuse across requests)",
    ),
    "serve.spec_accept_rate": (
        "gauge",
        "cumulative speculative tokens committed per per-row verify "
        "(1.0 = speculation buys nothing; draft_len + 1 is the ceiling)",
    ),
    "serve.page_evict": (
        "event",
        "pool pressure reclaimed an idle (refcount-0) prefix-cache page "
        "LRU-first; its cached prefix must be recomputed on next use",
    ),
    # Disaggregated prefill/decode + tiered KV (ISSUE 19): committed
    # page sets ship prefill→decode through kv_store, and evicted
    # prefix pages spill HBM → host DRAM → node-local disk instead of
    # being forgotten. All host-side; compile_stats() is unchanged.
    "serve.kv_ship": (
        "span",
        "prefill-role export: chunked prefill + page extraction + the "
        "atomic kv_store commit of one KVPageSet (prompt_len, pages, "
        "key) — the prefill half of a disaggregated admission",
    ),
    "serve.kv_import": (
        "span",
        "decode-role import: load + validate a committed KVPageSet at "
        "submit (ok=False = torn/missing/mismatched set → the request "
        "rides local prefill; the fallback evidence the chaos tests "
        "assert)",
    ),
    "serve.tier_hit": (
        "event",
        "a prompt's prefix-digest chain matched pages in a lower tier "
        "(host/disk counts); the pages promote back into the HBM pool "
        "instead of being recomputed by prefill",
    ),
    "serve.tier_promote": (
        "event",
        "tier-hit pages were written back into the HBM pool for an "
        "admission (pages, and whether prefill was skipped entirely)",
    ),
    "serve.tier_spill": (
        "event",
        "an evicted prefix page's content dropped to a lower tier "
        "(tier=host|disk) instead of being forgotten — still findable "
        "through the bounded digest→tier index",
    ),
    "serve.pages_host": (
        "gauge",
        "prefix pages currently held by the host-DRAM spill tier "
        "(TPUFLOW_KV_HOST_MB budget, LRU)",
    ),
    "serve.pages_disk": (
        "gauge",
        "prefix pages findable in the node-local disk spill tier "
        "(TPUFLOW_KV_DISK_DIR; survives engine restarts)",
    ),
    # Serving observatory (ISSUE 13): per-request lifecycle traces, the
    # engine-time ledger fractions, and declared-SLO accounting — the
    # serving analog of the goodput ledger (tpuflow.obs.serve_ledger),
    # read by `python -m tpuflow.obs serve-summary` and the timeline
    # card's Serving section.
    "serve.trace": (
        "event",
        "one request-lifecycle transition (request, phase=submitted|"
        "queued|admitted|first_token|tick|complete|drained, plus the "
        "phase's evidence: backpressure reason, bucket/pages, ttft_s, "
        "tokens committed per tick, finish reason); the full per-request "
        "record also lands in the obs/ access log",
    ),
    "serve.slo_violation": (
        "event",
        "a request violated a declared latency SLO (slo=ttft|itl, "
        "value, limit_s, group; armed by TPUFLOW_SERVE_SLO_TTFT_MS / "
        "TPUFLOW_SERVE_SLO_ITL_MS)",
    ),
    "serve.slo_violations": (
        "counter",
        "cumulative declared-SLO violations (TTFT + ITL) — the number "
        "tpu_watch --follow and /metrics surface",
    ),
    "serve.idle_fraction": (
        "gauge",
        "engine-time ledger: fraction of serve wall spent in the idle "
        "sleep (nothing queued, nothing live) — high idle with low "
        "queue depth means the replica is over-provisioned",
    ),
    "serve.decode_fraction": (
        "gauge",
        "engine-time ledger: fraction of serve wall inside the decode "
        "(+ verify) device dispatches — the bucket that earns tokens",
    ),
    "serve.prefill_fraction": (
        "gauge",
        "engine-time ledger: fraction of serve wall inside admission "
        "prefill dispatches — high under churny short-request traffic",
    ),
    "serve.decode_utilization": (
        "gauge",
        "occupancy-weighted decode utilization: live rows / batch rows "
        "summed over dispatched blocks (1.0 = every row of every block "
        "earned its FLOPs; low values say raise arrival rate or shrink "
        "slots)",
    ),
    "serve.decode_read_fraction": (
        "gauge",
        "cache positions the decode blocks gathered (rows x read width "
        "of the rung each ran at) over max_slots x n_ctx a block: 1.0 = "
        "every block read every slot's whole row; against "
        "decode_utilization x mean live context / n_ctx it sizes what a "
        "per-row (ragged) read would still save",
    ),
    "serve.tokens_per_pass": (
        "gauge",
        "tokens emitted over live rows x forward passes of the decode "
        "calls so far: 1.0 under one-token decode, block_length / "
        "(denoise_steps + 1) under block diffusion, where a third of the "
        "passes (at 2 denoise steps) are commits that yield none",
    ),
    "serve.pool_pad_fraction": (
        "gauge",
        "share of the page pool's bytes, and of every decode read's, "
        "that is zero lanes: a pool leaf keeps what a token holds as one "
        "vector padded to whole 128-lane rows (the shape the chip lays "
        "out page-major), so 0 for GPT-2's H*D and 0.1 for a 576-number "
        "latent in 640; set once when the engine allocates the pool",
    ),
    "serve.masked_row_waste": (
        "gauge",
        "fraction of dispatched batch rows live engine-wide but masked "
        "OUT of the dispatching group's program — what the "
        "(fp,int8)x(spec,plain) partition costs on mixed traffic",
    ),
    # Per-request int8 serving (ISSUE 9): the completion trail that lets
    # an operator split throughput by numeric path (the int8 twin of the
    # decode program is a serve.decode span with quant=true).
    "serve.quant_requests": (
        "counter",
        "completed requests that decoded through the int8 path (subset "
        "of serve.requests)",
    ),
    # --------------------------------------------------------------- fleet
    # Fleet observatory (ISSUE 14): replica discovery/registration, the
    # cross-replica poll sweep, and the staleness evidence trail —
    # emitted by tpuflow.obs.fleet / tpuflow.obs.export, read by
    # `python -m tpuflow.obs fleet-summary`, `tpu_watch --fleet`, and
    # the timeline card's Fleet section.
    "fleet.register": (
        "event",
        "this replica stamped its registration file into "
        "TPUFLOW_FLEET_REGISTRATION_DIR at export start (url, replica "
        "id, path) — how a fleet observatory discovers it without a "
        "static URL list",
    ),
    "fleet.poll": (
        "span",
        "one fleet poll sweep: discover replicas, poll every /status "
        "with per-replica timeout/backoff, aggregate one fleet snapshot",
    ),
    "fleet.size": (
        "gauge",
        "replicas the fleet observatory currently tracks (carries "
        "healthy= — the count with health score >= 0.5 and fresh "
        "/status)",
    ),
    "fleet.qps": (
        "gauge",
        "fleet aggregate completed-requests/s, summed from per-replica "
        "completion-counter deltas between successful polls",
    ),
    "fleet.replica_stale": (
        "event",
        "a replica aged past TPUFLOW_FLEET_STALE_S without a successful "
        "/status poll — unreachable, backing off, or answering "
        "malformed/truncated JSON (replica, url, age_s, last error); "
        "its health score pins to 0 until it answers again",
    ),
    # -------------------------------------------------------------- router
    # Front-door router (ISSUE 17): the admission/failover evidence
    # trail. A request's router events reconstruct its whole fleet
    # journey — admitted under what budget, forwarded where, rerouted
    # off which dead replica — without touching any replica's log.
    "router.admit": (
        "event",
        "a request cleared fleet token-budget admission and was "
        "dispatched (request id, replica, pages charged, queue wait s, "
        "affinity hit)",
    ),
    "router.reject": (
        "event",
        "a request exhausted its retry budget or timed out in the "
        "admission queue and returned 503 — the router's only loss "
        "mode, and it is explicit, bounded, and counted",
    ),
    "router.retry": (
        "event",
        "one forward attempt failed (timeout, refused, 5xx) and the "
        "request re-dispatched after backoff (request id, attempt, "
        "failed replica, error)",
    ),
    "router.reroute": (
        "event",
        "a retry landed on a different replica than the failed one — "
        "the transparent-failover case: replica died or stalled "
        "mid-request, client still sees exactly one answer",
    ),
    "router.drain": (
        "event",
        "a replica flipped serve_draining in its /status (SIGTERM "
        "landed): no new admissions route there; its queued-but-"
        "unstarted work re-enters the pick loop",
    ),
    "router.replace": (
        "event",
        "the autoscale loop launched a prewarm_cache-seeded "
        "replacement or requested scale-up (action, replica, reason: "
        "stale | occupancy | slo_rate)",
    ),
    "router.ship": (
        "event",
        "disaggregated serving (ISSUE 19): a long prompt's KV pages "
        "were prefilled on a prefill-role replica and committed — the "
        "decode forward carries the returned kv_key (request id, "
        "prefill replica, key)",
    ),
    "router.ship_fallback": (
        "event",
        "the KV ship hop failed (no prefill capacity, dead replica "
        "mid-ship, torn commit) and the request degraded to local "
        "prefill on the decode replica — never an error, never a "
        "drop, but counted so the degradation is observable",
    ),
    "router.queue_depth": (
        "gauge",
        "requests waiting in the front door's admission queue for "
        "fleet token budget (backpressure queues here, never drops)",
    ),
    "router.budget_pages": (
        "gauge",
        "fleet token budget the admission gate sees: summed pages_free "
        "over routable replicas minus pages the router has charged to "
        "in-flight requests",
    ),
    # --------------------------------------------------------------- trace
    "trace.escalate": (
        "event",
        "tail-sampling override: a head-unsampled trace force-recorded "
        "by an SLO breach, reroute, forward error, or queue timeout "
        "(trace id, request id, first reason) — the tail is never lost "
        "to the sampler",
    ),
    "trace.flush": (
        "event",
        "one recorded trace context drained its span buffer to the "
        "writer's trace JSONL (trace id, request id, span count, "
        "writer, escalation reason if any)",
    ),
    "trace.spans": (
        "counter",
        "spans appended to this process's trace-<writer>.jsonl (one "
        "O_APPEND write per flush — torn-tail-safe like the registry)",
    ),
    "trace.dropped": (
        "counter",
        "spans discarded because no trace directory resolves "
        "(TPUFLOW_TRACE_DIR unset and telemetry off) or the append "
        "failed — tracing never raises into the serving path",
    ),
    # --------------------------------------------------------------- quant
    "quant.decision": (
        "event",
        "quantization policy verdict at model load (mode, apply, float "
        "weight MiB, measured rationale) — emitted by maybe_quantize and "
        "by a quant-armed ServeEngine, so a run's events say which "
        "numeric path its decode took",
    ),
    "quant.kernel_fallback": (
        "event",
        "a forced-pallas int8 matmul shape could not tile (K/N % 128) "
        "and fell back to the XLA int8 path — numerics identical, "
        "recorded once per shape so a bench can attribute perf to the "
        "impl that actually ran",
    ),
    # ----------------------------------------------------------------- ops
    "ops.flash_bwd_fused": (
        "event",
        "a differentiated flash-attention call traced the fused one-"
        "kernel backward (seq/heads/causal/blocks)",
    ),
    # ---------------------------------------------------------------- dist
    "dist.mesh_generation": (
        "gauge",
        "the mesh generation this member (re-)initialized into (elastic "
        "gang: 0 at launch, bumped by every shrink/grow re-form; carries "
        "the member count and the resize reason)",
    ),
    # -------------------------------------------------------------- device
    "device.bytes_in_use": ("gauge", "sampled per-device HBM bytes in use"),
    "device.peak_bytes_in_use": ("gauge", "per-device peak HBM bytes"),
    # Device observatory (ISSUE 15): the per-program compile/memory
    # ledger, the throttled HBM gauges the StepClock/ServeEngine fences
    # feed, and the static budget check — emitted by tpuflow.obs.device,
    # read by `python -m tpuflow.obs device-summary`, the timeline
    # card's Device section, and the tpu_watch HBM segments.
    "device.program": (
        "event",
        "one compiled XLA program's ledger entry (program, compile_s, "
        "cost_analysis flops/bytes-accessed, memory_analysis argument/"
        "output/temp/generated-code bytes — absent keys where the "
        "backend can't report); the same record lands in the "
        "programs.json run artifact",
    ),
    "device.hbm_used": (
        "gauge",
        "HBM bytes in use on the busiest local device "
        "(memory_stats()['bytes_in_use'] max over devices), polled at "
        "the fences the hot loops already pay (TPUFLOW_DEVICE_POLL_S)",
    ),
    "device.hbm_peak": (
        "gauge",
        "peak HBM bytes on the busiest local device since process "
        "start (memory_stats()['peak_bytes_in_use'] max over devices)",
    ),
    "device.hbm_limit": (
        "gauge",
        "allocatable HBM bytes of the tightest local device "
        "(memory_stats()['bytes_limit'] min over devices — the device "
        "that OOMs first)",
    ),
    "device.hbm_budget": (
        "event",
        "static HBM budget verdict: resident program temp+argument "
        "bytes summed over the compiled inventory vs bytes_limit "
        "(over=True warns BEFORE an OOM; ratio keys absent off-TPU)",
    ),
    # -------------------------------------------------------------- alerts
    # Decision observatory (ISSUE 16): the run registry's append audit
    # trail and the alert engine's deduplicated lifecycle — emitted by
    # tpuflow.obs.registry / tpuflow.obs.alerts, read by the timeline
    # card's Alerts section, the /alerts endpoint, and the tpu_watch
    # ALERT lines.
    "registry.append": (
        "event",
        "one schema-versioned headline record appended to the "
        "TPUFLOW_REGISTRY_PATH run registry (path, run_id, kind, "
        "metric count) — the cross-run regression ledger's write "
        "audit",
    ),
    "alert.fired": (
        "event",
        "a declarative alert rule entered its firing condition "
        "(rule, severity, runbook anchor, message, value) — emitted "
        "ONCE per activation; the condition persisting is deduplicated",
    ),
    "alert.resolved": (
        "event",
        "an active alert's condition cleared after at least "
        "TPUFLOW_ALERT_COOLDOWN_S of activity (rule, severity, "
        "runbook, active_s) — flaps inside the cooldown never emit",
    ),
    # -------------------------------------------------------------- prof
    "prof.capture": (
        "event",
        "one anomaly-triggered bounded profiler capture committed "
        "(reason=step_time|itl|slo_ttft|slo_itl|nonfinite, trace dir, "
        "device-memory dump path, governor counters) — tpuflow.obs."
        "profcap, armed by TPUFLOW_PROF_TRIGGER",
    ),
    # -------------------------------------------------------------- health
    # Training-health observatory (ISSUE 3): per-step numerics computed
    # inside the jitted train step, emitted through the StepClock fences,
    # plus the divergence-policy events (tpuflow.obs.health).
    "health.loss": ("gauge", "per-step train loss (fenced host copy)"),
    "health.grad_norm": (
        "gauge",
        "pre-clip global gradient norm of the fenced step (spikes "
        "predict divergence; ~0 flags dead gradients)",
    ),
    "health.update_norm": (
        "gauge",
        "global norm of the applied parameter update (post-optimizer)",
    ),
    "health.param_norm": (
        "gauge",
        "global parameter norm after the step (drift/blowup evidence)",
    ),
    "health.nonfinite": (
        "counter",
        "steps whose fused on-device NaN/Inf flag fired (loss or grads)",
    ),
    "health.anomaly": (
        "event",
        "HealthMonitor detection: nonfinite streak, grad explosion, or "
        "median+MAD loss spike (kind, step, detector detail)",
    ),
    "health.rollback": (
        "event",
        "divergence auto-rollback: restored the last crc-verified "
        "checkpoint step (from_step → step, lr_scale when backed off)",
    ),
    "health.profile": (
        "event",
        "windowed jax.profiler capture committed (TPUFLOW_PROFILE="
        "start:stop): step window + trace directory",
    ),
    # ------------------------------------------------------------- goodput
    # Run observatory (ISSUE 6): goodput-so-far gauges emitted at the
    # fences StepClock already pays. The authoritative per-run ledger —
    # wall time decomposed into step/replay/compile/restore/data-wait/
    # ckpt/requeue-gap buckets — is computed by ``obs.summarize`` (and
    # ``Run.goodput()``) from the merged stream; these gauges are the
    # incremental in-run view the live export endpoint serves.
    "goodput.productive_s": (
        "gauge",
        "cumulative settled train-step seconds this process banked so "
        "far (the ledger's productive bucket)",
    ),
    "goodput.lost_s": (
        "gauge",
        "process wall seconds so far NOT spent in settled train steps "
        "(compile, restore, waits, gaps — decomposed precisely by the "
        "summarize-time goodput ledger)",
    ),
    "goodput.fraction": (
        "gauge",
        "productive fraction of this process's wall time so far "
        "(goodput-so-far; the run-level number comes from the merged "
        "ledger)",
    ),
    # ----------------------------------------------------------------- obs
    "obs.dropped": (
        "event",
        "telemetry events lost by this recorder (buffer overflow or a "
        "failed flush), surfaced once at close — never silently",
    ),
    "obs.flight": (
        "event",
        "a crash-forensics flight dump was written (reason + path): the "
        "bounded ring of recent events, env/config fingerprint, and "
        "faulting stack — referenced from the supervisor's "
        "flow.member_failed event",
    ),
    "obs.export": (
        "event",
        "the live metrics endpoint started serving /metrics (Prometheus "
        "text) + /status (JSON) on gang member 0 "
        "(TPUFLOW_OBS_HTTP_PORT); carries the bound port",
    ),
}


def kind_of(name: str) -> str:
    """Registered kind of ``name``; raises KeyError for unknown names."""
    return CATALOG[name][0]


def is_registered(name: str) -> bool:
    return name in CATALOG
