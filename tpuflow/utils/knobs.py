"""Central registry of every ``TPUFLOW_*`` environment knob.

Eleven PRs grew ~90 env knobs across the tree, read by scattered
``os.environ`` calls and documented (or not) by hand-maintained README
tables. Each of those hand-kept agreements rots silently: a typo'd name
(``..._SERVE_PAGES`` misspelled ``..._SERVE_PAGE``) silently defaults, a
knob added in code never reaches the README, a README row outlives the
code that read it. This module is the single source of truth the Orbax
checkpoint-as-contract argument (PAPERS.md) asks for, applied to the
knob surface:

- **Declarations.** ``REGISTRY`` holds one :class:`Knob` per name —
  type, default, subsystem, the README runbook anchor that explains it,
  and a one-line doc. ``internal=True`` marks launcher/test plumbing
  (e.g. ``TPUFLOW_ATTEMPT``) that operators never set by hand; those are
  documented in a separate "internal plumbing" table instead of the
  operator tables.
- **Typed accessors.** :func:`raw` / :func:`is_set` /
  :func:`get_str` / :func:`get_int` / :func:`get_float` /
  :func:`get_bool` all refuse undeclared names with a ``KeyError`` — a
  typo'd knob READ dies at the call instead of silently defaulting.
  ``raw`` is the migration workhorse: it returns exactly what
  ``os.environ.get`` returned so call sites with bespoke parsing
  (malformed-value fallbacks pinned by tests) keep their behavior
  bit-for-bit while becoming registry-visible.
- **README sync.** ``python -m tpuflow.utils.knobs --markdown`` emits
  the per-subsystem knob tables; the README embeds them between
  ``KNOB TABLES`` markers and ``--check`` verifies the region matches
  byte-for-byte. Pass 1 of ``tools/tpulint.py`` runs the same check, so
  a registry edit without a README regen fails lint.

Import discipline: stdlib only (``os``/``dataclasses``/``sys``) — the
lint, the standalone tools, and ``flows/`` import this without paying a
jax import.
"""

from __future__ import annotations

import dataclasses
import os

_UNSET = object()

# README anchors (GitHub heading slugs) the tables link to.
_A_FLOW = "fault-tolerance-runbook"
_A_ELASTIC = "elastic-gang-runbook"
_A_CKPT = "checkpoint-durability-runbook"
_A_CKPT_SUB = "checkpoint-subsystem"
_A_HEALTH = "training-health-runbook"
_A_STEP = "step-pipeline--performance-runbook"
_A_SERVE = "serving-runbook"
_A_FLEET = "fleet-observability-runbook"
_A_ROUTER = "router--failover-runbook"
_A_TRACE = "distributed-tracing-runbook"
_A_DEVICE = "device-observatory-runbook"
_A_QUANT = "quantization-runbook"
_A_KV = "disaggregated-serving-runbook"
_A_ALERTS = "regression--alerting-runbook"
_A_OBS = "goodput--live-monitoring-runbook"
_A_OBS_BASE = "observability"
_A_SETUP = "setup"
_A_FSDP = "gpt-2-fsdp-fully-sharded-training"
_A_DEPLOY = "deploy--schedule--trigger"
_A_LINT = "static-analysis-runbook"


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared ``TPUFLOW_*`` environment knob."""

    name: str
    type: str  # str | int | float | bool | path | enum | list
    default: object  # parsed-type default; None = unset/off
    doc: str
    subsystem: str
    anchor: str  # README heading slug the runbook row links to
    internal: bool = False  # launcher/test plumbing, not an operator knob
    choices: tuple = ()  # for type == "enum"
    default_doc: str = ""  # table override when repr(default) reads badly

    @property
    def shown_default(self) -> str:
        if self.default_doc:
            return self.default_doc
        if self.default is None:
            return "unset"
        if self.type == "bool":
            return "1" if self.default else "0"
        return str(self.default)


def _k(*args, **kw) -> tuple[str, Knob]:
    knob = Knob(*args, **kw)
    return knob.name, knob


REGISTRY: dict[str, Knob] = dict(
    (
        # ----------------------------------------------------------- flow
        _k("TPUFLOW_HOME", "path", "~/.tpuflow",
           "root for run/artifact storage and deployments", "flow",
           _A_SETUP),
        _k("TPUFLOW_NAMESPACE", "str", None,
           "namespace runs are produced under (default `user:<login>`)",
           "flow", _A_SETUP),
        _k("TPUFLOW_N_PARALLEL", "int", 1,
           "gang width the example flows launch: one process per HOST "
           "(it owns all local chips); N > 1 on one host is the CPU "
           "simulation and is refused on an accelerator", "flow",
           _A_SETUP),
        _k("TPUFLOW_TOPOLOGY", "str", "v5e-8",
           "TPU topology the @kubernetes example flows request",
           "flow", _A_DEPLOY),
        _k("TPUFLOW_GANG_TIMEOUT", "float", 300.0,
           "gang member join/rendezvous timeout (s)", "flow", _A_FLOW),
        _k("TPUFLOW_KILL_GRACE_S", "float", 5.0,
           "supervisor SIGTERM → SIGKILL escalation grace for gang "
           "members", "flow", _A_FLOW),
        _k("TPUFLOW_MAX_REQUEUES", "int", 8,
           "preemption requeues a step may consume (requeues never touch "
           "the retry budget)", "flow", _A_FLOW),
        _k("TPUFLOW_STALL_TIMEOUT_S", "float", 600.0,
           "heartbeat age past which a gang member is declared stalled",
           "flow", _A_FLOW),
        _k("TPUFLOW_GANG_REJOIN", "bool", True,
           "relaunch crashed/preempted capacity so an elastic gang can "
           "re-grow", "flow", _A_ELASTIC),
        _k("TPUFLOW_ELASTIC", "bool", False,
           "1 = resize the mesh on member loss instead of "
           "requeue-the-world", "flow", _A_ELASTIC),
        _k("TPUFLOW_GANG_MIN_MEMBERS", "int", 2,
           "shrink floor; below it member loss falls back to the classic "
           "requeue (`@tpu(min_members=...)` overrides)", "flow",
           _A_ELASTIC),
        _k("TPUFLOW_REFORM_TIMEOUT_S", "float", 120.0,
           "mesh re-form announce → all-joined deadline; missing it → "
           "classic requeue", "flow", _A_ELASTIC),
        _k("TPUFLOW_REFORM_WAIT_S", "float", 10.0,
           "how long a survivor's failed collective waits for a re-form "
           "plan before the error is treated as real", "flow", _A_ELASTIC),
        _k("TPUFLOW_MAX_RESIZES", "int", 8,
           "resize budget per gang step (a deterministic crasher must "
           "not shrink forever)", "flow", _A_ELASTIC),
        _k("TPUFLOW_REJOIN_HOLD_S", "float", 10.0,
           "hold a relaunch until every survivor's post-shrink heartbeat "
           "reappears (or this many seconds pass)", "flow", _A_ELASTIC),
        _k("TPUFLOW_FORCE_CPU", "bool", False,
           "1 = run flows and gang subprocesses on XLA:CPU virtual "
           "devices (the explicit CPU choice; nothing falls back to it)",
           "flow", _A_FLOW),
        # Launcher/member plumbing — stamped by the supervisor, read by
        # members; never set by operators.
        _k("TPUFLOW_ATTEMPT", "int", 0,
           "launch attempt number the supervisor stamps on each gang "
           "launch; keys goodput attempt lanes", "flow", _A_OBS,
           internal=True),
        _k("TPUFLOW_PROCESS_ID", "int", 0,
           "gang member rank the launcher assigns", "flow", _A_FLOW,
           internal=True),
        _k("TPUFLOW_NUM_PROCESSES", "int", 1,
           "gang world size the launcher assigns", "flow", _A_FLOW,
           internal=True),
        _k("TPUFLOW_COORDINATOR", "str", "127.0.0.1:42042",
           "jax.distributed coordinator address the launcher assigns",
           "flow", _A_FLOW, internal=True),
        _k("TPUFLOW_GANG_LOCAL_DEVICES", "int", 1,
           "virtual CPU devices per gang member on forced-CPU runs",
           "flow", _A_FLOW, internal=True),
        _k("TPUFLOW_MEMBERSHIP_DIR", "path", None,
           "elastic-gang rendezvous dir the supervisor assigns; its "
           "presence arms the membership runtime in members", "flow",
           _A_ELASTIC, internal=True),
        _k("TPUFLOW_FLOW", "str", None,
           "flow name the k8s manifest stamps into member pods", "flow",
           _A_DEPLOY, internal=True),
        _k("TPUFLOW_STEP", "str", None,
           "step name the k8s manifest stamps into member pods", "flow",
           _A_DEPLOY, internal=True),
        _k("TPUFLOW_RUN_ID", "str", None,
           "run id the k8s manifest stamps into member pods", "flow",
           _A_DEPLOY, internal=True),
        _k("TPUFLOW_REQUIREMENTS", "str", None,
           "pip requirements line the k8s manifest installs in member "
           "pods", "flow", _A_DEPLOY, internal=True),
        # ----------------------------------------------------------- dist
        _k("TPUFLOW_COMPILE_CACHE", "bool", True,
           "0 = disable the persistent XLA compile cache (on by default "
           "on accelerators, at JAX_COMPILATION_CACHE_DIR where set, else "
           ".compile_cache in the checkout)", "dist", _A_STEP),
        _k("TPUFLOW_COMPILE_CACHE_CPU", "bool", False,
           "1 = force-enable the persistent compile cache on CPU "
           "(default off: the XLA:CPU AOT reloader can SIGABRT across "
           "machine-feature changes)", "dist", _A_STEP),
        _k("TPUFLOW_COMM_OVERLAP", "bool", True,
           "0 = disable comm/compute overlap (per-microbatch "
           "reduce-scatter in the accum scan + async-collective libtpu "
           "flags)", "dist", _A_STEP),
        _k("TPUFLOW_DCN_DATA", "int", 0,
           "N = put the worker mesh's data axis on the DCN (multi-slice) "
           "axis at width N", "dist", _A_FSDP),
        # ---------------------------------------------------------- train
        _k("TPUFLOW_DISPATCH_DEPTH", "int", 2,
           "steps in flight before the hot loop settles the oldest "
           "step's scalars (1 = the old fully-synchronous loop)",
           "train", _A_STEP),
        _k("TPUFLOW_REMAT_POLICY", "enum", None,
           "remat selector for the train legs; env beats config, a typo "
           "fails at config time", "train", _A_STEP,
           choices=("full", "dots", "none"),
           default_doc="model preset's policy"),
        # ----------------------------------------------------------- data
        _k("TPUFLOW_DATA_DIR", "path", None,
           "dataset root (IDX/corpus files); unset → synthetic "
           "stand-ins", "data", _A_SETUP,
           default_doc="$TPUFLOW_HOME/data"),
        _k("TPUFLOW_TEXT_FILE", "path", None,
           "explicit LM corpus file (must exist — never degrades to "
           "synthetic)", "data", _A_SETUP),
        _k("TPUFLOW_FETCH", "bool", False,
           "1 = allow real dataset downloads (FileLock-guarded)",
           "data", _A_SETUP),
        _k("TPUFLOW_FETCH_BASE_URL", "str", None,
           "dataset download mirror override", "data", _A_SETUP),
        _k("TPUFLOW_SYNTH_TRAIN_N", "int", None,
           "synthetic dataset train-split size override", "data",
           _A_SETUP, default_doc="per dataset"),
        _k("TPUFLOW_SYNTH_TEST_N", "int", None,
           "synthetic dataset test-split size override", "data",
           _A_SETUP, default_doc="per dataset"),
        _k("TPUFLOW_PREFETCH_DEPTH", "int", 2,
           "batches buffered ahead by the device-put prefetch thread "
           "(0 = inline, no thread)", "data", _A_STEP),
        # ----------------------------------------------------------- ckpt
        _k("TPUFLOW_CKPT_FORMAT", "enum", "auto",
           "checkpoint format: native striped raw, orbax/ocdbt, or auto",
           "ckpt", _A_CKPT_SUB, choices=("auto", "raw", "orbax")),
        _k("TPUFLOW_CKPT_VERIFY", "bool", True,
           "0 = skip restore-side per-shard crc32 verification", "ckpt",
           _A_FLOW),
        _k("TPUFLOW_CKPT_MMAP", "bool", False,
           "1 = force mmap'd zero-copy restores process-wide (read-only "
           "consumers)", "ckpt", _A_CKPT_SUB),
        _k("TPUFLOW_CKPT_IO_RETRIES", "int", 4,
           "transient-failure retry budget per storage op (0 disables)",
           "ckpt", _A_CKPT),
        _k("TPUFLOW_CKPT_IO_BACKOFF_S", "float", 0.05,
           "base retry backoff (doubles per attempt, 50-100% jitter)",
           "ckpt", _A_CKPT),
        _k("TPUFLOW_CKPT_LOCAL_DIR", "path", None,
           "node-local fast checkpoint tier root (run-keyed; uploads to "
           "the persistent dir ride the saver thread)", "ckpt", _A_CKPT),
        _k("TPUFLOW_CKPT_LOCAL_KEEP", "int", 2,
           "newest committed steps kept in the local tier (oldest "
           "evicted first)", "ckpt", _A_CKPT),
        _k("TPUFLOW_WRITE_CONCURRENCY", "int", 0,
           "checkpoint shard-write pipeline width (0 = auto: 1 on "
           "memory-backed fs, else 4)", "ckpt", _A_CKPT_SUB),
        _k("TPUFLOW_IO_THREADS", "int", None,
           "explicit cap on total inflight checkpoint IO threads "
           "(wins over the restore floor of 4)", "ckpt", _A_CKPT_SUB,
           default_doc="min(cores, 16)"),
        _k("TPUFLOW_PREWARM_THREADS", "int", None,
           "page-backing prewarm threads (0 parks background prewarm, "
           ">=1 forces it)", "ckpt", _A_CKPT_SUB,
           default_doc="cores - 1"),
        _k("TPUFLOW_PREEMPT_GRACE_S", "float", None,
           "termination grace the preemption drain counts down from "
           "(gang launcher defaults it from TPUFLOW_KILL_GRACE_S; pods "
           "from terminationGracePeriodSeconds)", "ckpt", _A_CKPT),
        _k("TPUFLOW_PREEMPT_EMERGENCY_S", "float", 10.0,
           "remaining-grace threshold under which drains take the "
           "synchronous fastest-tier emergency save", "ckpt", _A_CKPT),
        # ------------------------------------------------------------ obs
        _k("TPUFLOW_OBS", "bool", True,
           "0 = disable the whole telemetry stream (recorder, ledger, "
           "export feed, flight ring)", "obs", _A_OBS_BASE),
        _k("TPUFLOW_OBS_MAX_BUFFERED", "int", 65536,
           "recorder buffer cap; overflow drops are counted and "
           "surfaced as a final obs.dropped event", "obs", _A_OBS_BASE),
        _k("TPUFLOW_OBS_FLIGHT_RING", "int", 256,
           "flight-recorder ring size (last events kept for the crash "
           "dump)", "obs", _A_OBS),
        _k("TPUFLOW_OBS_HTTP_PORT", "int", None,
           "live /metrics + /status export port on gang member 0 "
           "(0 = ephemeral; unset = no export)", "obs", _A_OBS),
        _k("TPUFLOW_OBS_HTTP_HOST", "str", "127.0.0.1",
           "live export bind host", "obs", _A_OBS),
        _k("TPUFLOW_OBS_DIR", "path", None,
           "telemetry dir a gang member inherits from the supervisor",
           "obs", _A_OBS_BASE, internal=True),
        _k("TPUFLOW_OBS_PROC", "int", None,
           "telemetry proc slot a gang member inherits from the "
           "supervisor", "obs", _A_OBS_BASE, internal=True),
        # --------------------------------------------------------- health
        _k("TPUFLOW_HEALTH", "bool", True,
           "0 = disable training-health monitoring entirely", "health",
           _A_HEALTH),
        _k("TPUFLOW_HEALTH_ROLLBACK", "bool", True,
           "0 = halt with a diagnostic instead of rolling back to the "
           "newest verified checkpoint", "health", _A_HEALTH),
        _k("TPUFLOW_HEALTH_NAN_BUDGET", "int", 1,
           "consecutive non-finite steps tolerated before an anomaly "
           "fires", "health", _A_HEALTH),
        _k("TPUFLOW_HEALTH_WINDOW", "int", 64,
           "rolling median/MAD loss window", "health", _A_HEALTH),
        _k("TPUFLOW_HEALTH_WARMUP", "int", 16,
           "observations before the spike detector judges", "health",
           _A_HEALTH),
        _k("TPUFLOW_HEALTH_SPIKE_MADS", "float", 12.0,
           "loss-spike threshold in MADs above the rolling median",
           "health", _A_HEALTH),
        _k("TPUFLOW_HEALTH_GRAD_MAX", "float", 0.0,
           "absolute grad-norm explosion threshold (0 = off)", "health",
           _A_HEALTH),
        _k("TPUFLOW_HEALTH_MAX_ROLLBACKS", "int", 2,
           "divergence rollbacks before halting anyway", "health",
           _A_HEALTH),
        _k("TPUFLOW_HEALTH_LR_BACKOFF", "float", 1.0,
           "peak-LR multiplier applied on each rollback (1.0 = off)",
           "health", _A_HEALTH),
        _k("TPUFLOW_PROFILE", "str", None,
           "`start:stop` step window wrapped in a jax.profiler trace",
           "health", _A_HEALTH),
        _k("TPUFLOW_PROFILE_DIR", "path", None,
           "profiler output dir outside a flow run", "health", _A_HEALTH),
        # ------------------------------------------------------------ ops
        _k("TPUFLOW_INT8_MATMUL", "enum", "auto",
           "int8 matmul impl: force xla/pallas, or auto-dispatch by "
           "shape", "quant", _A_QUANT,
           choices=("auto", "xla", "pallas")),
        _k("TPUFLOW_INT8_KERNEL_MIN_KN", "int", 262144,
           "min K*N weight-block size for the fused Pallas int8 kernel",
           "quant", _A_QUANT, default_doc="2^18"),
        # ---------------------------------------------------------- serve
        _k("TPUFLOW_SERVE", "bool", True,
           "0 = keep GenerationPredictor on the legacy per-batch-shape "
           "path", "serve", _A_SERVE),
        _k("TPUFLOW_SERVE_SLOTS", "int", 8,
           "decode slots (the fixed batch of the persistent program)",
           "serve", _A_SERVE),
        _k("TPUFLOW_SERVE_BUCKETS", "list", None,
           "comma prefill pad widths — the WHOLE prefill compile set",
           "serve", _A_SERVE,
           default_doc="power-of-two ladder → n_ctx - 1"),
        _k("TPUFLOW_SERVE_PREFILL_CHUNK", "int", None,
           "admission prefill chunk width (bounds peak attention "
           "memory)", "serve", _A_SERVE, default_doc="off"),
        _k("TPUFLOW_SERVE_DECODE_BLOCK", "int", 8,
           "tokens per decode dispatch (host syncs once per block)",
           "serve", _A_SERVE),
        _k("TPUFLOW_SERVE_QUANT", "str", None,
           "1/fused_native/weight_only arms per-request int8 decode",
           "serve", _A_SERVE, default_doc="off"),
        _k("TPUFLOW_SERVE_PAGE_SIZE", "int", 16,
           "tokens per KV page (must divide n_ctx; env values that "
           "don't degrade to a divisor)", "serve", _A_SERVE),
        _k("TPUFLOW_SERVE_PAGES", "int", None,
           "page-pool size; page 0 is the reserved trash page", "serve",
           _A_SERVE, default_doc="slots * n_ctx / page_size + 1"),
        _k("TPUFLOW_SERVE_PREFIX_CACHE", "bool", True,
           "0 = disable shared-prefix page reuse", "serve", _A_SERVE),
        _k("TPUFLOW_SERVE_SPEC", "int", None,
           "K arms per-request speculative decode at draft length K "
           "(submit(speculative=False) opts a request out)", "serve",
           _A_SERVE, default_doc="off"),
        _k("TPUFLOW_SERVE_TRACE", "bool", True,
           "0 = disarm per-request lifecycle traces (serve.trace "
           "events + the request's host-side trace list)", "serve",
           _A_SERVE),
        _k("TPUFLOW_SERVE_ACCESS_LOG", "bool", True,
           "0 = disarm the per-request JSONL access log "
           "(obs/access.p*.jsonl, read by `serve-summary`)", "serve",
           _A_SERVE),
        _k("TPUFLOW_SERVE_SLO_TTFT_MS", "float", None,
           "declared TTFT SLO in ms; a violating request emits "
           "serve.slo_violation and bumps the violation counter",
           "serve", _A_SERVE, default_doc="off"),
        _k("TPUFLOW_SERVE_SLO_ITL_MS", "float", None,
           "declared inter-token-latency SLO in ms, checked per decode "
           "tick (tick wall / tokens committed)", "serve", _A_SERVE,
           default_doc="off"),
        # ------------------------------------------- kv (disaggregation)
        _k("TPUFLOW_SERVE_ROLE", "enum", "both",
           "serving phase this replica advertises: a prefill replica "
           "takes the router's ship hops, a decode replica takes "
           "admissions (both = classic colocated serving; every "
           "existing path stays byte-identical)", "kv", _A_KV,
           choices=("prefill", "decode", "both")),
        _k("TPUFLOW_KV_STORE_DIR", "path", None,
           "committed KVPageSet store (the prefill→decode shipping "
           "layer: one crc-manifested blob per page set, tmp+rename "
           "commit, torn sets never load)", "kv", _A_KV),
        _k("TPUFLOW_KV_HOST_MB", "float", 0.0,
           "host-DRAM spill-tier budget in MiB for evicted-but-"
           "matchable prefix pages (0 = tier off; eviction forgets "
           "pages exactly as before)", "kv", _A_KV),
        _k("TPUFLOW_KV_DISK_DIR", "path", None,
           "node-local disk spill tier (same kv_store commit protocol; "
           "rescanned at engine start, so hot prefixes survive a "
           "replica restart)", "kv", _A_KV),
        _k("TPUFLOW_KV_DISK_MB", "float", 0.0,
           "disk spill-tier LRU budget in MiB (0 = unbounded; trimmed "
           "by manifest mtime after each spill)", "kv", _A_KV),
        _k("TPUFLOW_KV_INDEX_MAX", "int", 4096,
           "bound on the digest→tier index (the eviction-forgets-"
           "digests fix: spilled prefixes stay findable for promotion "
           "and router affinity without unbounded host state)", "kv",
           _A_KV),
        _k("TPUFLOW_KV_SHIP_MIN_TOKENS", "int", 0,
           "router: prompts at least this long take a prefill-replica "
           "hop before decode placement (0 = ship hop off; any "
           "ship-hop failure falls back to local prefill)", "kv",
           _A_KV),
        # ---------------------------------------------------------- fleet
        _k("TPUFLOW_FLEET_REPLICAS", "list", None,
           "comma list of replica /status base URLs the fleet "
           "observatory polls; a hostname resolving to multiple A "
           "records (headless Service) expands to one replica per pod",
           "fleet", _A_FLEET, default_doc="unset"),
        _k("TPUFLOW_FLEET_REGISTRATION_DIR", "path", None,
           "file-based replica registry: every exporting process stamps "
           "replica-<id>.json here at export start, and the fleet "
           "observatory discovers the fleet from the directory",
           "fleet", _A_FLEET),
        _k("TPUFLOW_FLEET_POLL_S", "float", 5.0,
           "fleet poll cadence (also the base of the per-replica "
           "failure backoff)", "fleet", _A_FLEET),
        _k("TPUFLOW_FLEET_STALE_S", "float", 15.0,
           "seconds without a successful /status poll before a replica "
           "is marked stale (health score 0; fleet.replica_stale event)",
           "fleet", _A_FLEET),
        _k("TPUFLOW_FLEET_HIST_BUCKETS", "list", None,
           "comma TTFT/ITL histogram bucket upper edges in seconds "
           "(strictly increasing); every replica of a fleet must agree "
           "or its buckets cannot merge", "fleet", _A_FLEET,
           default_doc="1ms..10s ladder"),
        _k("TPUFLOW_FLEET_SNAPSHOT_PATH", "path", None,
           "append one fleet-snapshot JSON line per poll here "
           "(post-hoc analysis trail)", "fleet", _A_FLEET),
        _k("TPUFLOW_FLEET_REPLICA_ID", "str", None,
           "replica identity stamped into /status and the registration "
           "file (the serving Deployment sets it from the pod name; "
           "default host-pid)", "fleet", _A_FLEET, internal=True),
        # --------------------------------------------------------- router
        _k("TPUFLOW_ROUTER_PORT", "int", 8900,
           "front-door HTTP bind port (0 = ephemeral; the router is the "
           "fleet's single client-facing ingress)", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_HOST", "str", "127.0.0.1",
           "front-door HTTP bind host (0.0.0.0 for a cluster ingress)",
           "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_TARGET", "str", None,
           "replica discovery target: a registration dir or comma "
           "/status URL list (default: the fleet observatory's "
           "discovery knobs)", "router", _A_ROUTER,
           default_doc="fleet knobs"),
        _k("TPUFLOW_ROUTER_GATEWAY", "bool", True,
           "0 = serve_forever skips its replica-side /generate gateway "
           "(the fleet row stays status-only and the front door cannot "
           "forward to this replica); the gateway shares the step "
           "loop's lock and advertises its URL as generate_url in "
           "/status", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_TIMEOUT_S", "float", 30.0,
           "per-replica forward timeout (s): a stalled replica is "
           "indistinguishable from a slow one until this expires, then "
           "the request re-dispatches", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_RETRIES", "int", 3,
           "forward retry budget per request; exhaustion returns 503 to "
           "the client (bounded, never a hang)", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_BACKOFF_S", "float", 0.05,
           "exponential-backoff base slept before retry k "
           "(base * 2^(k-1), capped at 2s)", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_AFFINITY", "bool", True,
           "0 = disable prefix-affine routing (requests sharing a "
           "prompt prefix pin to the replica already holding those "
           "pages — the fleet-wide prefix cache)", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_HEDGE", "bool", False,
           "1 = the first retry after a forward failure fires "
           "immediately (no backoff sleep) — lower rerouted-tail "
           "latency at the cost of load on an already-degraded fleet",
           "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_MIN_HEALTH", "float", 0.25,
           "fleet health-score floor for routing eligibility (stale "
           "replicas score 0 and are never routable)", "router",
           _A_ROUTER),
        _k("TPUFLOW_ROUTER_TREND_DECAY", "float", 0.5,
           "balance-score multiplier per consecutive queue-growth poll "
           "(score = health * decay^trend): a replica falling behind "
           "its arrivals sheds new work geometrically", "router",
           _A_ROUTER),
        _k("TPUFLOW_ROUTER_QUEUE_TIMEOUT_S", "float", 60.0,
           "max seconds a request waits in the admission queue for "
           "fleet token budget before 503 (backpressure queues, never "
           "drops — this is the bound that keeps the queue finite)",
           "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_AUTOSCALE", "bool", False,
           "1 = arm the autoscale/replacement loop: dead replicas get "
           "prewarm_cache-seeded replacements, sustained occupancy/SLO "
           "pressure requests scale-up", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_AUTOSCALE_OCC", "float", 0.85,
           "fleet mean slot-occupancy threshold above which the "
           "autoscale loop requests one scale-up", "router", _A_ROUTER),
        _k("TPUFLOW_ROUTER_AUTOSCALE_SLO", "float", 0.05,
           "fleet SLO violation rate (violations/requests) above which "
           "the autoscale loop requests one scale-up", "router",
           _A_ROUTER),
        _k("TPUFLOW_ROUTER_AUTOSCALE_COOLDOWN_S", "float", 120.0,
           "minimum seconds between autoscale actions per replica slot "
           "(replacements must not flap faster than pods can start)",
           "router", _A_ROUTER),
        # --------------------------------------------------------- device
        _k("TPUFLOW_DEVICE_POLL_S", "float", 10.0,
           "HBM gauge poll cadence (s) at the fences the hot loops "
           "already pay (0 disables; backends without memory_stats "
           "disable themselves after the first probe)", "device",
           _A_DEVICE),
        _k("TPUFLOW_DEVICE_LEDGER", "bool", True,
           "0 = skip per-program compile/memory ledger collection "
           "(programs.json + device.program events) at the warmup/"
           "compile fences and serve start", "device", _A_DEVICE),
        _k("TPUFLOW_PROF_TRIGGER", "bool", False,
           "1 = arm anomaly-triggered profiler capture: step-time/ITL "
           "median+MAD spikes, SLO breaches, and nonfinite steps arm a "
           "bounded jax.profiler trace + device memory dump", "device",
           _A_DEVICE),
        _k("TPUFLOW_PROF_ZMADS", "float", 8.0,
           "anomaly trigger threshold in robust MADs above the rolling "
           "median (step-time and ITL detectors)", "device", _A_DEVICE),
        _k("TPUFLOW_PROF_COOLDOWN_S", "float", 300.0,
           "minimum seconds between triggered captures (the governor's "
           "rate bound)", "device", _A_DEVICE),
        _k("TPUFLOW_PROF_MAX_CAPTURES", "int", 3,
           "per-run triggered-capture cap; past it triggers are counted "
           "but suppressed", "device", _A_DEVICE),
        _k("TPUFLOW_PROF_TRACE_STEPS", "int", 2,
           "observations (train steps / decode ticks) one triggered "
           "trace spans before it stops — the capture's size bound",
           "device", _A_DEVICE),
        _k("TPUFLOW_PROF_DIR", "path", None,
           "triggered-capture output dir when telemetry is disabled "
           "(default <obs_dir>/profile)", "device", _A_DEVICE),
        # --------------------------------------------------------- alerts
        _k("TPUFLOW_REGISTRY_PATH", "path", None,
           "run-registry JSONL: every training run and serving run "
           "appends one schema-versioned headline record here (unset = "
           "implicit run-end appends off)", "alerts", _A_ALERTS,
           default_doc="unset"),
        _k("TPUFLOW_REGISTRY_WINDOW", "int", 5,
           "trailing runs the trend/verdict median+MAD window spans",
           "alerts", _A_ALERTS),
        _k("TPUFLOW_REGISTRY_ZMADS", "float", 8.0,
           "regression threshold in robust MADs from the trailing "
           "median (the PR 15 detector idiom, host-side)",
           "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_SLO_BUDGET", "float", 0.01,
           "SLO violation-rate budget the burn-rate windows are "
           "measured against (violations / requests)", "alerts",
           _A_ALERTS),
        _k("TPUFLOW_ALERT_FAST_WINDOW_S", "float", 300.0,
           "fast burn-rate window (s); the page needs the fast AND "
           "slow windows both over budget", "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_SLOW_WINDOW_S", "float", 3600.0,
           "slow burn-rate window (s) — proves the burn is sustained, "
           "not one bad minute", "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_HBM_HEADROOM", "float", 0.08,
           "free-HBM fraction floor (tightest device/replica); "
           "headroom under it fires hbm_headroom", "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_GOODPUT_MIN", "float", 0.5,
           "goodput-fraction floor; a settled run (steps > 0) under it "
           "fires goodput_drop", "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_MIN_HEALTH", "float", 0.5,
           "worst-replica health-score floor; a fleet under it fires "
           "health_collapse", "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_COOLDOWN_S", "float", 60.0,
           "minimum seconds an alert stays active before it may "
           "resolve (anti-flap hold)", "alerts", _A_ALERTS),
        _k("TPUFLOW_ALERT_REROUTE_RATE", "float", 0.1,
           "router reroute rate (reroutes / completed requests over "
           "the fast window) past which reroute_spike fires — "
           "sustained rerouting means replicas are dying or stalling "
           "faster than the fleet absorbs", "alerts", _A_ROUTER),
        _k("TPUFLOW_ALERT_ROUTER_TTFT_FRAC", "float", 0.5,
           "fraction of the fleet TTFT p95 the router-side wait per "
           "request (fast window) may reach before "
           "ttft_router_dominance fires — past it, latency lives in "
           "router admission, not the replicas", "alerts", _A_TRACE),
        # ---------------------------------------------------------- trace
        _k("TPUFLOW_TRACE", "bool", True,
           "0 = disarm end-to-end request tracing (context minting, "
           "propagation, and span recording; the disarmed fast path "
           "is one `is not None` check per integration point)",
           "trace", _A_TRACE),
        _k("TPUFLOW_TRACE_SAMPLE", "float", 1.0,
           "head-sample rate for ingress-minted traces (0..1); SLO "
           "breach, reroute, forward error, and queue timeout always "
           "record regardless (tail sampling)", "trace", _A_TRACE),
        _k("TPUFLOW_TRACE_DIR", "path", None,
           "trace-span JSONL directory override (default: "
           "<obs_dir>/trace beside the recorder's event fragments; "
           "unset with telemetry off = spans counted dropped)",
           "trace", _A_TRACE, default_doc="unset"),
        # -------------------------------------------------------- testing
        _k("TPUFLOW_FAULT", "str", None,
           "comma-separated fault-injection specs (chaos suite)",
           "testing", _A_FLOW),
        _k("TPUFLOW_CRASH_SENTINEL", "path", None,
           "sentinel file chaos tests use to fire a crash exactly once",
           "testing", _A_FLOW, internal=True),
        _k("TPUFLOW_TEST_CKPT_DIR", "path", None,
           "checkpoint dir chaos-test gang snippets inherit", "testing",
           _A_FLOW, internal=True),
        _k("TPUFLOW_HEARTBEAT_FILE", "path", None,
           "member heartbeat file the supervisor assigns", "flow",
           _A_FLOW, internal=True),
        # ---------------------------------------------------------- flows
        _k("TPUFLOW_STORAGE", "path", "/tmp/tpuflow_run",
           "checkpoint storage path the example custom-Trainer flow "
           "uses", "flow", _A_SETUP),
    )
)

# The operator-facing subsystem tables, in README order.
_SUBSYSTEM_TITLES = (
    ("flow", "Flow orchestration & gangs"),
    ("dist", "Distributed runtime"),
    ("train", "Training step pipeline"),
    ("data", "Data"),
    ("ckpt", "Checkpointing & preemption"),
    ("obs", "Observability"),
    ("health", "Training health"),
    ("ops", "Kernels & dispatch"),
    ("quant", "Quantization"),
    ("serve", "Serving"),
    ("kv", "Disaggregated serving & KV tiers"),
    ("fleet", "Fleet observatory"),
    ("router", "Front-door router"),
    ("trace", "Distributed tracing"),
    ("device", "Device observatory"),
    ("alerts", "Run registry & alerting"),
    ("testing", "Fault injection & testing"),
)

MARKDOWN_BEGIN = (
    "<!-- BEGIN KNOB TABLES (generated by "
    "`python -m tpuflow.utils.knobs --markdown`; edit the registry in "
    "tpuflow/utils/knobs.py, then regenerate — tools/tpulint.py pass 1 "
    "fails on drift) -->"
)
MARKDOWN_END = "<!-- END KNOB TABLES -->"


# ------------------------------------------------------------- accessors
def _declared(name: str) -> Knob:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"undeclared knob {name!r}: every TPUFLOW_* env knob must be "
            "declared in tpuflow/utils/knobs.py REGISTRY (this is how "
            "typo'd names die loudly instead of silently defaulting — "
            "tools/tpulint.py pass 1 enforces the same contract "
            "statically)"
        ) from None


def raw(name: str, default: str | None = None) -> str | None:
    """``os.environ.get`` with a declaration check — the migration
    workhorse for call sites whose parsing conventions (malformed-value
    fallbacks, bespoke truthiness sets) are pinned by tests."""
    _declared(name)
    return os.environ.get(name, default)


def is_set(name: str) -> bool:
    _declared(name)
    return name in os.environ


def get_str(name: str, default=_UNSET):
    knob = _declared(name)
    val = os.environ.get(name)
    if val is None:
        return knob.default if default is _UNSET else default
    return val


def get_int(name: str, default=_UNSET):
    """Typed read; unset → registry default (or the call-site override).
    A malformed value raises ``ValueError`` naming the knob — same
    failure the bare ``int(os.environ[...])`` sites always had, now with
    a useful message."""
    knob = _declared(name)
    val = os.environ.get(name)
    if val is None or val == "":
        return knob.default if default is _UNSET else default
    try:
        return int(val)
    except ValueError:
        raise ValueError(f"{name}={val!r} is not an integer") from None


def get_float(name: str, default=_UNSET):
    knob = _declared(name)
    val = os.environ.get(name)
    if val is None or val == "":
        return knob.default if default is _UNSET else default
    try:
        return float(val)
    except ValueError:
        raise ValueError(f"{name}={val!r} is not a number") from None


def get_int_lenient(name: str, default=_UNSET):
    """Like :func:`get_int` but a malformed value falls back to the
    default instead of raising — the convention of knobs that must never
    kill a run mid-provisioning on a typo (dispatch depth, prefetch
    depth, checkpoint IO retries; their fallbacks are pinned by tests)."""
    knob = _declared(name)
    val = os.environ.get(name)
    if val is None or val == "":
        return knob.default if default is _UNSET else default
    try:
        return int(val)
    except ValueError:
        return knob.default if default is _UNSET else default


def get_float_lenient(name: str, default=_UNSET):
    knob = _declared(name)
    val = os.environ.get(name)
    if val is None or val == "":
        return knob.default if default is _UNSET else default
    try:
        return float(val)
    except ValueError:
        return knob.default if default is _UNSET else default


_FALSY = ("0", "false", "off", "no")


def get_bool(name: str, default=_UNSET):
    """Truthy unless ``0/false/off/no`` (case-insensitive) — the
    convention the comm-overlap knobs pinned in tests. Sites with a
    narrower falsy set read through :func:`raw` instead."""
    knob = _declared(name)
    val = os.environ.get(name)
    if val is None:
        return knob.default if default is _UNSET else default
    return val.strip().lower() not in _FALSY


# ------------------------------------------------------------- markdown
def _table(rows: list[Knob]) -> list[str]:
    out = [
        "| Knob | Type | Default | What it does |",
        "| --- | --- | --- | --- |",
    ]
    for k in rows:
        typ = k.type
        if k.type == "enum" and k.choices:
            typ = " \\| ".join(k.choices)
        out.append(
            f"| `{k.name}` | {typ} | `{k.shown_default}` | {k.doc} "
            f"([runbook](#{k.anchor})) |"
        )
    return out


def markdown() -> str:
    """The generated README knob-reference region (between the
    ``KNOB TABLES`` markers), one table per subsystem plus the internal
    plumbing table."""
    lines = [MARKDOWN_BEGIN, ""]
    lines.append(
        f"{len(REGISTRY)} knobs are declared in "
        "`tpuflow/utils/knobs.py`; every `TPUFLOW_*` read anywhere in "
        "the tree goes through its typed accessors "
        "(`tools/tpulint.py` pass 1). Regenerate this section with "
        "`python -m tpuflow.utils.knobs --markdown`."
    )
    for sub, title in _SUBSYSTEM_TITLES:
        rows = [
            k for k in REGISTRY.values()
            if k.subsystem == sub and not k.internal
        ]
        if not rows:
            continue
        lines += ["", f"### {title} knobs", ""]
        lines += _table(sorted(rows, key=lambda k: k.name))
    internal = [k for k in REGISTRY.values() if k.internal]
    lines += [
        "",
        "### Internal plumbing (not operator knobs)",
        "",
        "Stamped by the supervisor/launcher/tests and read back by "
        "members — set them by hand and the runbooks above stop "
        "describing your system.",
        "",
        "| Knob | Stamped by | Meaning |",
        "| --- | --- | --- |",
    ]
    for k in sorted(internal, key=lambda k: k.name):
        lines.append(f"| `{k.name}` | {k.subsystem} | {k.doc} |")
    lines += ["", MARKDOWN_END]
    return "\n".join(lines)


def readme_region(readme_text: str) -> str | None:
    """The current generated region in ``readme_text`` (markers
    inclusive), or None when the markers are absent/torn."""
    try:
        start = readme_text.index(MARKDOWN_BEGIN)
        end = readme_text.index(MARKDOWN_END) + len(MARKDOWN_END)
    except ValueError:
        return None
    if end <= start:
        return None
    return readme_text[start:end]


def check_readme(readme_path: str) -> list[str]:
    """Error strings when the README's generated region is missing or
    stale (``--check``; tpulint pass 1 calls this)."""
    try:
        with open(readme_path) as f:
            text = f.read()
    except OSError as e:
        return [f"cannot read {readme_path}: {e}"]
    region = readme_region(text)
    if region is None:
        return [
            f"{readme_path}: knob-table markers not found — paste the "
            "output of `python -m tpuflow.utils.knobs --markdown` into "
            "the README"
        ]
    if region != markdown():
        return [
            f"{readme_path}: knob tables are stale — regenerate with "
            "`python -m tpuflow.utils.knobs --markdown` (registry and "
            "README must agree byte-for-byte)"
        ]
    return []


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description="TPUFLOW_* knob registry: emit or verify the README "
        "knob tables"
    )
    p.add_argument("--markdown", action="store_true",
                   help="print the generated README knob-table region")
    p.add_argument("--check", action="store_true",
                   help="exit 1 when the README region is stale")
    p.add_argument("--list", action="store_true",
                   help="one line per declared knob")
    p.add_argument("--readme", default=None,
                   help="README path (default: repo root README.md)")
    args = p.parse_args(argv)
    readme = args.readme or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "README.md",
    )
    if args.list:
        for k in sorted(REGISTRY.values(), key=lambda k: k.name):
            flag = " [internal]" if k.internal else ""
            print(f"{k.name}  ({k.type}, default {k.shown_default})"
                  f"{flag} — {k.doc}")
        return 0
    if args.check:
        errors = check_readme(readme)
        for e in errors:
            print(f"[knobs] ERROR: {e}")
        if not errors:
            print(f"[knobs] ok ({len(REGISTRY)} knobs, README in sync)")
        return 1 if errors else 0
    if args.markdown:
        print(markdown())
        return 0
    p.print_help()
    return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
