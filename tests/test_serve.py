"""Continuous-batching serving engine (tpuflow.infer.serve, ISSUE 8;
paged KV + shared-prefix reuse + per-request speculative decode,
ISSUE 11).

The load-bearing contracts:

- **Token exactness.** Every request decoded through the engine —
  admitted into a reused slot, left-padded to a bucket width, scattered
  across pool pages, batched beside unrelated sequences, drafted-and-
  verified speculatively — produces exactly the greedy tokens of a solo
  ``generate()`` of its prompt (decode_precision pinning from PR 4
  makes batched decode width-independent; int8 contractions are
  integer-exact).
- **Never recompiles after warmup.** One persistent decode program (+
  verify block when spec-armed), one insert pair, a bounded
  prefill-bucket set: the jit cache sizes after ``warmup()`` never grow
  across admissions, evictions, eos exits, slot reuse, page allocation,
  and prefix sharing — page tables are DATA.
- **Page accounting is host-pure.** PagePool (allocation, refcounts,
  prefix matching, LRU eviction, backpressure) is plain python/numpy —
  its edge cases are pinned with zero compiles.
- **Chunked-prefill admission boundaries.** Prompt lengths exactly on /
  one off a chunk boundary, pad_lens interaction, and bucket reuse all
  decode token-exactly with zero fresh compiles per admission.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuflow.infer import generate
from tpuflow.infer.serve import (
    ServeEngine,
    default_buckets,
    resolve_buckets,
    serve_forever,
)
from tpuflow.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def model_params():
    cfg = GPT2Config.small_test(n_ctx=64, dropout=0.0)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def engine(model_params):
    """One warmed 2-slot PAGED engine shared by the fast tests (the
    engine is long-lived by design; sharing it across tests IS the
    contract). page_size=8 puts page boundaries inside the fast tests'
    prompt lengths, so the shared programs double as the page-boundary
    exactness coverage."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8,
    )
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def scan_model_params():
    cfg = GPT2Config.small_test(n_ctx=64, dropout=0.0, scan_layers=True)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def scan_engine(scan_model_params):
    """The shared engine's twin under ``scan_layers``: the layer-stacked
    pool, carried through the layer scan (ISSUE 27) — the layout the
    benchmark's serving cell runs."""
    model, params = scan_model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8,
    )
    eng.warmup()
    return eng


@pytest.fixture(params=["blocks", "scan"])
def served(request):
    """``(engine, (model, params))`` for each paged cache layout: one pool
    per block (the shared fixture engine) and the layer-stacked pool."""
    prefix = "" if request.param == "blocks" else "scan_"
    return (
        request.getfixturevalue(prefix + "engine"),
        request.getfixturevalue(prefix + "model_params"),
    )


def _solo(model, params, prompt, n_new, **kw):
    return np.asarray(
        generate(
            model, params, np.asarray(prompt, np.int32)[None, :],
            max_new_tokens=n_new, temperature=0.0, **kw,
        )
    )[0]


# ------------------------------------------------------------ pure units
def test_page_pool_accounting():
    """PagePool host-side edges with zero compiles: trash-page reserve,
    allocation, backpressure, prefix chain matching + self-registration,
    refcounts across sharers, idle retention, and LRU eviction."""
    from tpuflow.infer.serve import PagePool

    pool = PagePool(n_pages=6, page_size=4)  # pages 1..5 usable
    assert pool.usable_pages == 5 and pool.free_pages == 5
    prompt = np.arange(10, dtype=np.int32)  # 2 full pages + 2 tokens
    digests = pool.prefix_digests(prompt)
    assert len(digests) == 2  # only FULLY prompt-covered pages hash
    assert pool.match_len(digests) == 0
    ids, matched = pool.acquire(prompt, 3)
    assert matched == 0 and len(ids) == 3 and 0 not in ids
    assert pool.free_pages == 2 and pool.allocated_pages == 3
    # Second request, same prefix: the 2 full prompt pages are shared.
    ids2, matched2 = pool.acquire(prompt, 3)
    assert matched2 == 2 and ids2[:2] == ids[:2] and ids2[2] != ids[2]
    assert pool.free_pages == 1  # one fresh page for the second request
    assert pool.prefix_hits == 2
    # Backpressure: a request needing 2 fresh pages cannot fit.
    other = np.arange(100, 112, dtype=np.int32)
    assert pool.acquire(other, 2) is None
    # Release the first request: shared pages stay (the second request
    # still holds them, refcount 1), its private page frees.
    pool.release(ids)
    assert pool.free_pages == 2 and pool.allocated_pages == 3
    # Release the second: the prefix pages go IDLE (still matchable).
    pool.release(ids2)
    assert pool.allocated_pages == 0 and pool.free_pages == 5
    assert pool.match_len(digests) == 2
    # A matching request reactivates the idle pages without eviction.
    ids3, matched3 = pool.acquire(prompt, 2)
    assert matched3 == 2 and ids3 == ids[:2] and pool.evictions == 0
    # Pool pressure evicts idle cached pages LRU-first.
    pool.release(ids3)
    ids4, m4 = pool.acquire(other, 5)
    assert m4 == 0 and len(ids4) == 5
    assert pool.evictions == 2  # both idle prefix pages reclaimed
    assert pool.match_len(digests) == 0
    # prefix_cache=False: nothing hashes, nothing shares.
    flat = PagePool(n_pages=4, page_size=2, prefix_cache=False)
    assert flat.prefix_digests(prompt) == []
    a, m = flat.acquire(prompt, 2)
    b, m2 = flat.acquire(prompt, 1)
    assert m == m2 == 0 and not set(a) & set(b)
    with pytest.raises(ValueError, match="n_pages"):
        PagePool(n_pages=1, page_size=4)


def test_ngram_draft_host():
    from tpuflow.infer.speculative import ngram_draft

    # Repetitive history: the 2-gram (8, 9) recurs — draft continues it.
    h = np.array([7, 8, 9, 7, 8, 9, 7, 8, 9], np.int32)
    np.testing.assert_array_equal(ngram_draft(h, 3), [7, 8, 9])
    # Most RECENT occurrence wins.
    h2 = np.array([1, 2, 3, 1, 2, 4, 1, 2], np.int32)
    np.testing.assert_array_equal(ngram_draft(h2, 2), [4, 1])
    # Ladder falls to 1-gram when the full gram never recurs.
    h3 = np.array([5, 6, 9, 1, 9], np.int32)
    np.testing.assert_array_equal(ngram_draft(h3, 2), [1, 9])
    # No repetition at all: repeat-last-token fallback.
    h4 = np.array([1, 2, 3], np.int32)
    np.testing.assert_array_equal(ngram_draft(h4, 3), [3, 3, 3])
    # Draft shorter than the tail pads with the last history token.
    h5 = np.array([4, 5, 4, 5], np.int32)
    out = ngram_draft(h5, 4)
    assert out.shape == (4,)
    with pytest.raises(ValueError, match="non-empty"):
        ngram_draft(np.array([], np.int32), 2)


@pytest.mark.parametrize("who", ["engine", "engine_speculative", "model"])
def test_slot_row_layout_is_refused(model_params, who):
    """The contiguous slot rows went with PR 32: the engine's ``paged``
    keyword stays for the benchmark's files alone and refuses False, and
    the model refuses per-row positions without a page table."""
    model, params = model_params
    if who == "model":
        with pytest.raises(ValueError, match="slot_index passed without"):
            jax.eval_shape(
                lambda p: model.apply(
                    {"params": p}, jnp.zeros((2, 1), jnp.int32),
                    decode=True, mutable=["cache"],
                    slot_index=jnp.zeros((2,), jnp.int32),
                ),
                params,
            )
    else:
        kw = {"speculative": 2} if who == "engine_speculative" else {}
        with pytest.raises(ValueError, match="paged=False"):
            ServeEngine(model, params, paged=False, **kw)


def test_resolve_paged_knobs(monkeypatch):
    from tpuflow.infer.serve import resolve_page_size, resolve_spec_draft

    monkeypatch.delenv("TPUFLOW_SERVE_PAGE_SIZE", raising=False)
    monkeypatch.delenv("TPUFLOW_SERVE_SPEC", raising=False)
    assert resolve_page_size(1024) == 16
    assert resolve_page_size(64, 8) == 8
    with pytest.raises(ValueError, match="divide"):
        resolve_page_size(64, 7)  # explicit bad arg raises
    monkeypatch.setenv("TPUFLOW_SERVE_PAGE_SIZE", "7")
    assert resolve_page_size(64) == 4  # env degrades to a divisor
    monkeypatch.setenv("TPUFLOW_SERVE_PAGE_SIZE", "banana")
    assert resolve_page_size(64) == 16
    assert resolve_spec_draft() == 0
    assert resolve_spec_draft(True) == 4
    assert resolve_spec_draft(3) == 3
    assert resolve_spec_draft(False) == 0
    with pytest.raises(ValueError, match=">= 0"):
        resolve_spec_draft(-1)
    monkeypatch.setenv("TPUFLOW_SERVE_SPEC", "5")
    assert resolve_spec_draft() == 5
    monkeypatch.setenv("TPUFLOW_SERVE_SPEC", "yes-please")
    assert resolve_spec_draft() == 0  # malformed env: off, loudly


def test_bucket_ladders_and_env(monkeypatch):
    # The n_ctx bucket is never admittable (capacity is checked on the
    # PADDED width and max_new_tokens >= 1), so ladders top at n_ctx - 1.
    assert default_buckets(1024) == [16, 32, 64, 128, 256, 512, 1023]
    assert default_buckets(64) == [16, 32, 63]
    assert default_buckets(8) == [7]
    assert resolve_buckets(128, [64, 16, 64, 200]) == [16, 64]
    with pytest.raises(ValueError, match="bucket"):
        resolve_buckets(128, [128, 999])
    monkeypatch.setenv("TPUFLOW_SERVE_BUCKETS", "8,32")
    assert resolve_buckets(128) == [8, 32]
    monkeypatch.setenv("TPUFLOW_SERVE_BUCKETS", "banana")
    assert resolve_buckets(64) == default_buckets(64)


def test_submit_validation_and_bucket_for(engine):
    # Smallest bucket holding the prompt whose padded width still fits
    # the budget: n_ctx=64, buckets [8, 16].
    assert engine.bucket_for(3, 10) == 8
    assert engine.bucket_for(9, 10) == 16
    with pytest.raises(ValueError, match="no prefill bucket"):
        engine.bucket_for(17, 10)  # longer than every bucket
    with pytest.raises(ValueError, match="no prefill bucket"):
        engine.bucket_for(9, 60)  # bucket 16 + 60 > n_ctx
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError, match="at least one token"):
        engine.submit([], max_new_tokens=4)


def test_serve_ledger_feeds_metrics_export():
    """The process ledger's serve_* keys (fed by the engine each
    iteration) reach the /metrics Prometheus rendering — the live
    operator surface tools/tpu_watch.py --follow reads. Ledger-only:
    no engine needed to pin the export mapping."""
    from tpuflow.obs.export import prometheus_text
    from tpuflow.obs.goodput import ProcessLedger

    led = ProcessLedger()
    snap = led.snapshot()
    assert "serve_queue_depth" not in snap  # training runs: no serve keys
    led.note_serve_state(queue_depth=3, live_slots=2, max_slots=4)
    led.note_serve_tokens(10)
    time.sleep(0.01)
    led.note_serve_tokens(30)
    led.note_serve_ttft(0.25)
    led.note_serve_ttft(0.05)
    led.note_serve_complete()
    snap = led.snapshot()
    assert "serve_pages_free" not in snap  # no pool reported yet: no keys
    assert "serve_spec_accept_rate" not in snap
    led.note_serve_pages(free=12, total=16)
    led.note_serve_prefix(hits=3, lookups=4)
    led.note_serve_spec(committed=21, forwards=10)
    snap = led.snapshot()
    assert snap["serve_queue_depth"] == 3
    assert snap["serve_slot_occupancy"] == 0.5
    assert snap["serve_requests"] == 1
    assert snap["serve_tokens"] == 40
    assert snap["serve_tokens_per_s"] > 0
    assert snap["serve_ttft_p50_s"] == pytest.approx(0.25)
    assert snap["serve_ttft_p99_s"] == pytest.approx(0.25)
    assert snap["serve_pages_free"] == 12
    assert snap["serve_prefix_hit_rate"] == 0.75
    assert snap["serve_spec_accept_rate"] == 2.1
    text = prometheus_text(snap)
    assert "tpuflow_serve_tokens_total 40" in text
    assert "tpuflow_serve_queue_depth 3" in text
    assert "tpuflow_serve_ttft_p50_seconds 0.25" in text
    assert "tpuflow_serve_pages_free 12" in text
    assert "tpuflow_serve_prefix_hit_rate 0.75" in text
    assert "tpuflow_serve_spec_accept_rate 2.1" in text


# ---------------------------------------- serving observatory (ISSUE 13)
def test_lifecycle_trace_ledger_slo_and_access_log(
    engine, model_params, tmp_path
):
    """The observatory through the SHARED warmed engine (zero fresh
    compiles): a staggered multi-request run gives every request a
    trace with exactly one terminal event, the engine-time ledger's
    buckets sum to the measured serve wall within 5% (exact by cursor
    construction), forced SLOs emit serve.slo_violation events + the
    counter, the access log lands one line per terminal request, and
    the serve-summary CLI reads it back — with compile_stats()
    unchanged, tracing/SLO/access-log all armed (the acceptance's
    never-recompile clause)."""
    from tpuflow import obs
    from tpuflow.obs.__main__ import main as obs_main
    from tpuflow.obs.serve_ledger import load_access_log, summarize_access

    model, params = model_params
    run_dir = str(tmp_path / "run")
    base = engine.compile_stats()
    led0 = obs.goodput_live()
    obs.configure(os.path.join(run_dir, "obs"), proc=0)
    try:
        engine.ledger.reset()
        engine.ledger.slo_ttft_s = 1e-9  # everything violates: SLO path
        engine.ledger.slo_itl_s = 1e-9
        rng = np.random.default_rng(31)
        prompts = [
            rng.integers(0, 512, size=L).astype(np.int32)
            for L in (3, 9, 5)
        ]
        # Staggered: two up front (fills both slots), the third joins
        # mid-decode and must trace a queued/slots backpressure phase.
        r1 = engine.submit(prompts[0], max_new_tokens=6)
        r2 = engine.submit(prompts[1], max_new_tokens=6)
        r3 = engine.submit(prompts[2], max_new_tokens=5)
        engine.step()
        engine.run_until_idle(max_iters=200)
        reqs = [r1, r2, r3]
        for p, r, n in zip(prompts, reqs, (6, 6, 5)):
            np.testing.assert_array_equal(
                r.result(), _solo(model, params, p, n)
            )
        # Exactly one terminal transition per submitted request.
        for r in reqs:
            phases = [t["phase"] for t in r.trace]
            assert phases[0] == "submitted"
            assert phases.count("complete") == 1
            assert phases.count("drained") == 0
            assert r.terminal_phase == "complete"
            assert "admitted" in phases and "first_token" in phases
            assert "tick" in phases
            assert r.itl_s, "no per-tick ITL observations"
            assert r.slo_violations >= 1  # forced TTFT SLO at least
        assert any(
            t["phase"] == "queued" and t["reason"] == "slots"
            for t in r3.trace
        ), r3.trace
        # Ledger: buckets sum to the measured engine wall within 5%
        # (cursor construction makes them equal; 5% is the acceptance
        # slack), with real prefill/decode/insert charges.
        snap = engine.ledger.snapshot()
        assert sum(snap["buckets"].values()) == pytest.approx(
            snap["wall_s"], rel=0.05
        )
        assert snap["buckets"]["prefill"] > 0
        assert snap["buckets"]["decode"] > 0
        assert snap["buckets"]["insert"] > 0
        assert snap["decode_utilization"] is not None
        assert snap["slo_violations"] >= 3
        assert "fp.plain" in snap["ttft"] and "fp.plain" in snap["itl"]
        # The live process ledger carries the observatory keys /metrics
        # renders (fractions, ITL percentiles, SLO count).
        ps = led0.snapshot()
        for key in (
            "serve_idle_fraction", "serve_decode_fraction",
            "serve_prefill_fraction", "serve_itl_p99_s",
            "serve_slo_violations",
        ):
            assert key in ps, key
        # The event stream carries the trace + SLO evidence.
        obs.flush()
        events = []
        d = os.path.join(run_dir, "obs")
        for name in os.listdir(d):
            if name.startswith("events."):
                events.extend(obs.read_events(os.path.join(d, name)))
        names = {(e["kind"], e["name"]) for e in events}
        assert ("event", "serve.trace") in names
        assert ("event", "serve.slo_violation") in names
        assert ("counter", "serve.slo_violations") in names
        assert ("gauge", "serve.idle_fraction") in names
        assert ("gauge", "serve.decode_fraction") in names
        assert ("gauge", "serve.prefill_fraction") in names
        # Access log: one line per terminal request; serve-summary
        # reproduces the percentile view from it alone.
        records = load_access_log(run_dir)
        assert len(records) == 3
        assert {r["request"] for r in records} == {x.id for x in reqs}
        s = summarize_access(records)
        assert s["requests"] == 3 and s["ttft"]["count"] == 3
        assert s["itl"]["count"] == sum(len(r.itl_s) for r in reqs)
        assert obs_main(["serve-summary", run_dir]) == 0
        # Never-recompile with the whole observatory armed.
        assert engine.compile_stats() == base, "observatory recompiled"
    finally:
        engine.ledger.slo_ttft_s = None
        engine.ledger.slo_itl_s = None
        engine._access = None
        obs.configure(None)


def test_drain_queued_traces_terminal(engine):
    """drain_queued (the SIGTERM drain path) terminal-traces every
    still-queued request as drained — idempotently — while leaving the
    queue intact for the requeue; a later resumed run may still
    complete them (the trace then records the resumed completion)."""
    r = engine.submit([1, 2, 3], max_new_tokens=3)
    assert engine.drain_queued() == 1
    assert r.terminal_phase == "drained" and not r.done
    assert engine.queue_depth == 1  # queue preserved for the requeue
    assert engine.drain_queued() == 0  # idempotent: one terminal only
    assert sum(
        1 for t in r.trace if t["phase"] == "drained"
    ) == 1
    # Leave the shared engine clean; the resumed engine completes it.
    engine.run_until_idle(max_iters=100)
    assert r.done and r.terminal_phase == "complete"


def test_fleet_registration_histogram_export_never_recompile(
    engine, model_params, monkeypatch, tmp_path
):
    """Fleet observatory (ISSUE 14) through the SHARED warmed engine:
    with the registration dir + live export armed, export start stamps
    a registration file carrying the replica identity, /status carries
    that identity plus the mergeable TTFT/ITL histogram buckets, and
    /metrics renders them in the Prometheus histogram convention — all
    host-side, with compile_stats() unchanged (the acceptance's
    never-recompile clause: registration + histogram export armed)."""
    import json as _json
    import urllib.request

    from tpuflow import obs
    from tpuflow.obs import export as obs_export
    from tpuflow.obs import fleet as fleet_mod

    model, params = model_params
    base = engine.compile_stats()
    reg = str(tmp_path / "fleet")
    monkeypatch.setenv("TPUFLOW_FLEET_REGISTRATION_DIR", reg)
    monkeypatch.setenv("TPUFLOW_FLEET_REPLICA_ID", "test-replica-0")
    monkeypatch.setenv("TPUFLOW_OBS_HTTP_PORT", "0")
    obs_export.stop()
    try:
        server = obs.maybe_start_export(proc=0)
        assert server is not None
        (rec,) = fleet_mod.read_registrations(reg)
        assert rec["url"] == server.url
        assert rec["replica"]["id"] == "test-replica-0"
        # Serve through the shared engine while the exporter is live.
        p = np.arange(1, 6, dtype=np.int32)
        r = engine.submit(p, max_new_tokens=4)
        engine.run_until_idle(max_iters=200)
        np.testing.assert_array_equal(
            r.result(), _solo(model, params, p, 4)
        )
        with urllib.request.urlopen(
            server.url + "/status", timeout=5
        ) as resp:
            st = _json.loads(resp.read().decode())
        assert st["replica"]["id"] == "test-replica-0"
        hist = st["serve_ttft_hist"]
        assert hist["count"] >= 1
        assert len(hist["counts"]) == len(hist["edges"]) + 1
        assert sum(hist["counts"]) == hist["count"]
        with urllib.request.urlopen(
            server.url + "/metrics", timeout=5
        ) as resp:
            text = resp.read().decode()
        assert "# TYPE tpuflow_serve_ttft_seconds histogram" in text
        assert 'tpuflow_serve_ttft_seconds_bucket{le="+Inf"}' in text
        # The fleet observatory polls this live replica end to end.
        snap = fleet_mod.FleetObservatory(reg, stale_s=30.0).poll()
        (row,) = snap["replicas"]
        assert row["id"] == "test-replica-0" and not row["stale"]
        assert snap["fleet"]["ttft"]["count"] == hist["count"]
        assert engine.compile_stats() == base, (
            "fleet registration/histogram export recompiled"
        )
    finally:
        obs_export.stop()


def test_alert_engine_and_registry_never_recompile(
    engine, model_params, monkeypatch, tmp_path
):
    """Decision observatory (ISSUE 16) through the SHARED warmed
    engine: the alert engine consumes this engine's REAL live
    snapshots (forced-SLO traffic so the burn-rate counters actually
    move) and walks the exact fired -> resolved lifecycle — dedup'd in
    between — then the run-end registry hook appends this replica's
    headline (TTFT p99 from the mergeable buckets) from the same
    snapshot, all host-side with compile_stats() unchanged (the
    acceptance's never-recompile clause with everything armed)."""
    from tpuflow import obs
    from tpuflow.obs import alerts as alerts_mod
    from tpuflow.obs import registry as registry_mod

    model, params = model_params
    base = engine.compile_stats()
    reg_path = str(tmp_path / "reg.jsonl")
    monkeypatch.setenv("TPUFLOW_REGISTRY_PATH", reg_path)

    t = {"now": 0.0}
    eng = alerts_mod.AlertEngine(
        clock=lambda: t["now"], slo_budget=0.01, fast_window_s=300.0,
        slow_window_s=600.0, cooldown_s=0.0,
    )
    seq = []

    def sweep():
        snap = obs.goodput_live().snapshot()
        seq.extend(
            (x["rule"], x["state"]) for x in eng.observe(status=snap)
        )

    sweep()  # single baseline sample: windows cannot judge, no fire
    assert seq == []
    engine.ledger.slo_ttft_s = 1e-9  # every request violates
    try:
        for _ in range(2):
            t["now"] += 150.0
            p = np.arange(1, 6, dtype=np.int32)
            r = engine.submit(p, max_new_tokens=4)
            engine.run_until_idle(max_iters=200)
            np.testing.assert_array_equal(
                r.result(), _solo(model, params, p, 4)
            )
            sweep()
    finally:
        engine.ledger.slo_ttft_s = None
    # Fired on the first judgeable burning sweep, then dedup'd.
    assert seq == [("slo_burn_rate", "fired")]
    # Clean traffic after the windows age the burn out: the AND-gate
    # releases and (cooldown 0) the alert resolves exactly once.
    t["now"] += 10_000.0
    for _ in range(2):
        t["now"] += 100.0
        p = np.arange(1, 6, dtype=np.int32)
        r = engine.submit(p, max_new_tokens=4)
        engine.run_until_idle(max_iters=200)
        sweep()
    assert seq == [
        ("slo_burn_rate", "fired"), ("slo_burn_rate", "resolved"),
    ]
    assert eng.active() == []
    # The serve_forever run-end hook's append, from the live snapshot.
    snap = obs.goodput_live().snapshot()
    assert registry_mod.maybe_append_live("serve", snap) is True
    (rec,) = registry_mod.read_registry(reg_path)
    assert rec["kind"] == "serve"
    assert rec["metrics"]["serve_requests"] >= 1
    assert "serve_ttft_p99_s" in rec["metrics"]
    assert engine.compile_stats() == base, (
        "alert engine / registry armed recompiled"
    )


def test_serve_trace_disarmed_is_one_bool_check(engine):
    """TPUFLOW_SERVE_TRACE=0 semantics: with _trace_on False the trace
    hook records nothing — no list growth, no events — and the engine
    still serves exactly (the TPUFLOW_OBS=0 overhead twin lives in
    tests/test_obs.py)."""
    old = engine._trace_on
    engine._trace_on = False
    try:
        r = engine.submit([5, 6, 7], max_new_tokens=3)
        engine.run_until_idle(max_iters=100)
        assert r.done and r.trace == [] and r.terminal_phase is None
    finally:
        engine._trace_on = old


def test_trace_id_stamping_backcompat(engine):
    """ISSUE 18 back-compat pin: serving WITHOUT the front door (no
    propagated TraceContext) produces lifecycle phases, serve.* event
    attrs, and access rows with NO ``trace_id`` key at all — absent,
    never an empty string — while a propagated context stamps its id
    everywhere. The end-to-end integration lives in the chaos tier;
    this pins the exact dict shapes."""
    from tpuflow.obs import trace as reqtrace

    # Untraced: submit() without trace= leaves trace_ctx None and the
    # lifecycle phase dicts carry no trace_id.
    r = engine.submit([5, 6], max_new_tokens=2)
    engine.run_until_idle(max_iters=100)
    assert r.trace_ctx is None and r.done
    assert r.trace  # lifecycle recorded...
    assert all("trace_id" not in p for p in r.trace)  # ...unstamped
    assert ServeEngine._tid(engine, r) == {}

    # Traced: the propagated context's id stamps phases and _tid.
    ctx = reqtrace.TraceContext("f" * 32, "0" * 16, "tr-1", sampled=True)
    r2 = engine.submit([5, 6, 7], max_new_tokens=2, trace=ctx)
    assert r2.trace_ctx is ctx
    engine.run_until_idle(max_iters=100)
    assert r2.done
    assert all(p["trace_id"] == "f" * 32 for p in r2.trace)
    assert ServeEngine._tid(engine, r2) == {"trace_id": "f" * 32}
    # The terminal transition flushed the replica half of the trace
    # through flush_lifecycle (buffer drained on the context).
    assert ctx.spans == []


# ------------------------------------------------- engine decode contracts
def test_unequal_requests_token_exact_and_never_recompile(served):
    """Four unequal-length requests through TWO slots (so admissions wait
    on evictions and slots are reused), with an eos early-exit in the
    mix: every request equals its solo generate(), and the jit caches
    never grow past warmup."""
    engine, (model, params) = served
    base = engine.compile_stats()
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(0, 512, size=L).astype(np.int32)
        for L in (3, 8, 11, 6)
    ]
    reqs = [
        engine.submit(p, max_new_tokens=7) for p in prompts
    ]
    engine.run_until_idle(max_iters=200)
    for p, r in zip(prompts, reqs):
        want = _solo(model, params, p, 7)
        np.testing.assert_array_equal(r.result(), want)
        assert r.done and r.finish_reason == "budget"
        assert r.ttft_s is not None and r.ttft_s >= 0
        assert r.decode_tokens_per_s is None or r.decode_tokens_per_s > 0
    # eos: the eos token itself is emitted, then the slot frees early.
    want = _solo(model, params, prompts[0], 7)
    eos = int(want[3])
    r = engine.submit(prompts[0], max_new_tokens=7, eos_id=eos)
    engine.run_until_idle(max_iters=200)
    assert r.finish_reason == "eos"
    # (through the token's FIRST occurrence: it may come before index 3)
    assert r.tokens == list(want[: list(want).index(eos) + 1])
    # max_new_tokens=1 completes at admission (prefill's argmax IS the
    # one token); the slot is never occupied.
    r1 = engine.submit(prompts[1], max_new_tokens=1)
    engine.run_until_idle(max_iters=10)
    assert r1.done and r1.tokens == [int(_solo(model, params, prompts[1], 1)[0])]
    assert engine.compile_stats() == base, "engine recompiled after warmup"
    assert engine.live_slots == 0 and engine.queue_depth == 0


def test_interleaved_submission_mid_decode(served):
    """Requests submitted WHILE others decode (the continuous-batching
    case: admission interleaves with decode blocks) stay token-exact."""
    engine, (model, params) = served
    base = engine.compile_stats()
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 512, size=5).astype(np.int32)
    p2 = rng.integers(0, 512, size=12).astype(np.int32)
    p3 = rng.integers(0, 512, size=7).astype(np.int32)
    r1 = engine.submit(p1, max_new_tokens=9)
    engine.step()  # admit r1, first decode block
    assert engine.live_slots == 1
    r2 = engine.submit(p2, max_new_tokens=5)
    engine.step()  # r2 admitted beside mid-flight r1
    r3 = engine.submit(p3, max_new_tokens=6)
    engine.run_until_idle(max_iters=200)
    for p, r, n in ((p1, r1, 9), (p2, r2, 5), (p3, r3, 6)):
        np.testing.assert_array_equal(
            r.result(), _solo(model, params, p, n)
        )
    assert engine.compile_stats() == base


def test_page_boundary_lengths_exact(served):
    """Page-boundary edges through the SHARED fixture engine (page_size
    8 — zero fresh compiles): prompt length one under / on / one over a
    page boundary, with budgets landing the final frontier on and
    around page multiples, all token-exact vs solo generate()."""
    engine, (model, params) = served
    base = engine.compile_stats()
    rng = np.random.default_rng(21)
    for L, n in ((7, 7), (8, 7), (9, 7), (8, 8)):
        p = rng.integers(0, 512, size=L).astype(np.int32)
        r = engine.submit(p, max_new_tokens=n)
        engine.run_until_idle(max_iters=100)
        np.testing.assert_array_equal(
            r.result(), _solo(model, params, p, n)
        )
        assert r.finish_reason == "budget"
    assert engine.compile_stats() == base, "page edges recompiled"
    # Pages held by finished requests are all released.
    assert engine.pool.allocated_pages == 0


# ------------------------------------- the decode ladder (ISSUE 33)
@pytest.fixture(scope="module")
def ladder_run():
    """A warmed 4-slot engine over 256 positions in pages of 16 (decode
    shapes 1 x 8, 1 x 16, 2 x 16 and 4 x 16 pages), and one run through
    it in which the long request's row crosses the width rung while it
    decodes and the live count crosses the row rungs upward (admissions)
    and downward (completions). Returns what the cases below read."""
    cfg = GPT2Config.small_test(n_ctx=256, dropout=0.0, scan_layers=True)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    eng = ServeEngine(
        model, params, max_slots=4, buckets=[32, 128], decode_block=4,
        page_size=16,
    )
    warm = eng.warmup()
    rungs: list[tuple[int, int, int]] = []  # (live rows, rows, pages)
    pick = eng._decode_rung

    def spy(mask):
        rows, pages = pick(mask)
        rungs.append((int(mask.sum()), len(rows), pages))
        return rows, pages

    eng._decode_rung = spy
    rng = np.random.default_rng(33)
    plan = {  # name: (prompt length, tokens out)
        "long": (110, 60), "first": (20, 6), "second": (30, 10),
        "third": (9, 14), "fourth": (40, 7),
    }
    prompts = {
        k: rng.integers(0, 512, size=L).astype(np.int32)
        for k, (L, _) in plan.items()
    }

    def submit(k):
        return eng.submit(prompts[k], max_new_tokens=plan[k][1])

    reqs = {"long": submit("long")}
    for _ in range(7):  # alone: 110 + 4 fits 128 positions, 126 + 4 not
        eng.step()
    reqs["first"] = submit("first")
    while not reqs["first"].done:  # two live, then one
        eng.step()
    for k in ("second", "third", "fourth"):  # four live, then fewer
        reqs[k] = submit(k)
    eng.run_until_idle(max_iters=400)
    return {
        "model": model, "params": params, "eng": eng, "warm": warm,
        "after": eng.compile_stats(), "rungs": rungs, "plan": plan,
        "prompts": prompts, "reqs": reqs,
    }


@pytest.mark.parametrize(
    "name", ["long", "first", "second", "third", "fourth"]
)
def test_ladder_tokens_equal_generate(ladder_run, name):
    """Whatever shapes a request's blocks ran at, beside whichever rows,
    its tokens are solo ``generate()``'s."""
    r = ladder_run
    np.testing.assert_array_equal(
        r["reqs"][name].result(),
        _solo(r["model"], r["params"], r["prompts"][name],
              r["plan"][name][1]),
    )
    assert r["reqs"][name].finish_reason == "budget"


@pytest.mark.parametrize(
    "what", ["compiled", "width_up", "rows_up", "rows_down", "smallest"]
)
def test_ladder_rungs_visited_and_never_recompile(ladder_run, what):
    r = ladder_run
    eng, rungs = r["eng"], r["rungs"]
    shapes = [(rows, pages) for _, rows, pages in rungs]
    if what == "compiled":
        # warmup() compiles the ladder, all of it and nothing else.
        assert eng.decode_shapes == [(1, 8), (1, 16), (2, 16), (4, 16)]
        assert r["warm"]["decode"] == len(eng.decode_shapes)
        assert r["after"] == r["warm"], "a shape compiled after warmup"
    elif what == "width_up":
        # The long row, alone: half the width, then all of it.
        assert shapes[0] == (1, 8) and (1, 16) in shapes
        widths = [pages for _, pages in shapes]
        assert widths == sorted(widths)
    elif what == "rows_up":
        assert any(a < b for (a, _), (b, _) in zip(shapes, shapes[1:]))
        assert max(rows for rows, _ in shapes) == 4
    elif what == "rows_down":
        assert any(a > b for (a, _), (b, _) in zip(shapes, shapes[1:]))
        assert shapes[-1] == (1, 16)
    else:
        # Every block ran at the smallest row count that held its live
        # rows, and every shape of the ladder was run.
        for live, rows, _ in rungs:
            assert rows == min(x for x, _ in eng.decode_shapes if x >= live)
        assert set(shapes) == set(eng.decode_shapes)
        assert eng.ledger.decode_read_fraction < 0.75


def _slot_state(eng, live, lengths, group=None):
    """Set a cold engine's host state: which slots are live, how long
    their rows are, and which of them belong to the group asked about
    (default: all the live ones)."""
    S = eng.max_slots
    eng._live = np.zeros((S,), bool)
    eng._live[list(live)] = True
    eng._lengths = np.zeros((S,), np.int32)
    eng._lengths[list(live)] = lengths
    mask = np.zeros((S,), bool)
    mask[list(live if group is None else group)] = True
    return mask


_RUNG_CASES = {
    # name: (live slots, their lengths, the group or None, rows, pages)
    "quarter-live-short": ([2, 5, 11], [300, 272, 500], None, 4, 32),
    "reach-on-the-rung": ([0], [504], None, 4, 32),
    "reach-past-the-rung": ([0], [505], None, 4, 64),
    "five-live-short": (range(5), [280, 290, 300, 310, 320], None, 8, 64),
    "five-live-one-long": (range(5), [280, 290, 300, 310, 760], None, 8, 64),
    "row-at-the-end": ([7], [1020], None, 4, 64),
    "all-live": (range(16), [400] * 16, None, 16, 64),
    "group-beside-a-full-house": (range(16), [400] * 16, [3, 4, 9], 4, 32),
}


@pytest.fixture(scope="module")
def cell_shape_engine():
    """A cold engine (zero weights, nothing compiled) with the serving
    cell's slots, positions and pages around the tests' tiny model."""
    model = GPT2(GPT2Config.small_test(n_ctx=1024, dropout=0.0))
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0),
        ),
    )
    return ServeEngine(
        model, params, max_slots=16, buckets=[16], decode_block=8,
        page_size=16,
    )


@pytest.mark.parametrize("case", sorted(_RUNG_CASES))
def test_decode_rung_choice(cell_shape_engine, case):
    """``_decode_rung`` at the serving cell's geometry (16 slots of
    1,024 positions, pages of 16): the first shape of the ladder that
    holds the group and its frontier; the rows are the group's live
    slots first, padded with dead slots and only then with other groups'
    live ones; a slot set with no dead slot to pad with takes what is
    there, and all 16 live take the top rung."""
    eng = cell_shape_engine
    assert eng.decode_shapes == [(4, 32), (4, 64), (8, 64), (16, 64)]
    live, lengths, group, want_rows, want_pages = _RUNG_CASES[case]
    mask = _slot_state(eng, live, lengths, group)
    rows, pages = eng._decode_rung(mask)
    assert (len(rows), pages) == (want_rows, want_pages)
    assert len(set(rows.tolist())) == len(rows)
    members = np.flatnonzero(mask)
    np.testing.assert_array_equal(rows[: len(members)], members)
    padding = rows[len(members):]
    dead = int((~eng._live).sum())
    assert not eng._live[padding[:dead]].any()  # dead slots come first
    assert eng._live[padding[dead:]].all()


@pytest.mark.parametrize("slots, n_ctx, want", [
    (16, 1024, [(4, 32), (4, 64), (8, 64), (16, 64)]),  # serve-medium-chat: as PR 33 left it
    (8, 1024, [(2, 32), (2, 64), (4, 64), (8, 64)]),
    (32, 4096, [(8, 128), (8, 256), (16, 256), (24, 256), (32, 256)]),  # serve-xing4-reason
    (64, 4096, [(16, 128)] + [(r, 256) for r in range(16, 65, 8)]),
    (1, 64, [(1, 8)]),
])
def test_decode_ladder_steps_by_at_most_eight_rows(slots, n_ctx, want):
    """A quarter, a half and all of the slots, and no shape more than 8
    rows above the one below: up to 16 slots the four shapes of PR 33."""
    from tpuflow.infer.serve import decode_ladder

    shapes = decode_ladder(slots, n_ctx, 16 if n_ctx > 64 else 8)
    assert shapes == want
    rows = sorted({r for r, _ in shapes})
    assert rows[-1] == slots
    assert all(b - a <= 8 for a, b in zip(rows, rows[1:]))


# ------------------------------------------- paged engine (ISSUE 11, slow)
@pytest.mark.slow
def test_prefix_cache_reuse_eviction_and_residency(model_params):
    """Shared-prefix page reuse end to end: two requests whose prompts
    share a 2-page system prefix decode bit-equal to solo generate()
    while the second SHARES the first's prefix pages (refcounted, hit-
    counted); after release the pages idle in the cache, a matching
    third request reactivates them, and pool pressure evicts them
    LRU-first with a serve.page_evict trail. Residency efficiency beats
    what a contiguous ``n_ctx`` row a live slot would strand."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16, 32], decode_block=4,
        page_size=8, n_pages=9,  # 8 usable pages: tight enough to evict
    )
    base = eng.warmup()
    rng = np.random.default_rng(22)
    pre = rng.integers(0, 512, size=16).astype(np.int32)  # 2 full pages
    pa = np.concatenate([pre, rng.integers(0, 512, size=3).astype(np.int32)])
    pb = np.concatenate([pre, rng.integers(0, 512, size=5).astype(np.int32)])
    ra = eng.submit(pa, max_new_tokens=5)
    eng.step()
    # Mid-flight admission shares the LIVE request's prefix pages.
    rb = eng.submit(pb, max_new_tokens=5)
    eng.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(ra.result(), _solo(model, params, pa, 5))
    np.testing.assert_array_equal(rb.result(), _solo(model, params, pb, 5))
    assert eng.pool.prefix_hits == 2  # rb reused both prefix pages
    assert eng.pool.evictions == 0
    # All request pages released; the 2 prefix pages idle in the cache.
    assert eng.pool.allocated_pages == 0
    assert eng.pool.free_pages == 8
    # Reactivation: a third sharer allocates only its private tail.
    rc = eng.submit(pa, max_new_tokens=4)
    eng.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(rc.result(), _solo(model, params, pa, 4))
    assert eng.pool.prefix_hits == 4 and eng.pool.evictions == 0
    # Pressure: a fat unrelated request needs every free page -> the
    # idle prefix pages are evicted (LRU), never the trash page.
    fat = rng.integers(0, 512, size=30).astype(np.int32)
    rf = eng.submit(fat, max_new_tokens=30)  # ceil(60/8) = 8 pages
    eng.run_until_idle(max_iters=300)
    np.testing.assert_array_equal(
        rf.result(), _solo(model, params, fat, 30)
    )
    assert eng.pool.evictions == 2
    assert eng.compile_stats() == base, "paged engine recompiled"
    # Residency: short requests keep most allocated tokens resident,
    # where a contiguous layout would strand an n_ctx row a live slot.
    # max_new outlives one decode block so the sample sees a live slot.
    r1 = eng.submit(pa, max_new_tokens=6)
    eng.step()
    paged_res = eng.residency_efficiency()
    live = np.nonzero(eng._live)[0]
    resident = int((eng._lengths[live] - eng._pads[live]).sum())
    flat_res = resident / (live.size * eng.n_ctx)
    eng.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(r1.result(), _solo(model, params, pa, 6))
    assert live.size == 1 and resident >= pa.size
    assert paged_res == resident / (4 * eng.page_size)  # ceil(25 / 8) pages
    assert paged_res > flat_res, (paged_res, flat_res)


@pytest.mark.slow
def test_page_pool_exhaustion_backpressure(model_params):
    """Pool exhaustion = admission BACKPRESSURE: the head-of-queue
    request waits (queued, never dropped) while a free slot exists but
    pages don't, admits as soon as a finishing request releases pages,
    and decodes exactly."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8], decode_block=4,
        page_size=8, n_pages=3, prefix_cache=False,  # 2 usable pages
    )
    eng.warmup()
    rng = np.random.default_rng(23)
    p = rng.integers(0, 512, size=4).astype(np.int32)
    q1 = eng.submit(p, max_new_tokens=8)  # needs ceil(12/8) = 2 pages
    q2 = eng.submit(p, max_new_tokens=8)  # needs 2 more: must wait
    eng.step()
    assert q1.state == "running"
    assert q2.state == "queued" and eng.queue_depth == 1
    assert eng._free_slot() is not None  # a slot IS free; pages are not
    eng.run_until_idle(max_iters=300)
    assert q1.done and q2.done
    np.testing.assert_array_equal(q2.result(), _solo(model, params, p, 8))
    # A request that could NEVER fit the pool fails eagerly at submit.
    with pytest.raises(ValueError, match="pool"):
        eng.submit(rng.integers(0, 512, size=8).astype(np.int32),
                   max_new_tokens=20)


@pytest.mark.slow
def test_speculative_engine_token_exact(model_params):
    """Per-request speculative decode inside the batched block: a
    repetitive prompt (high n-gram acceptance) and a random prompt (low
    acceptance) decode BIT-equal to solo generate() side by side; eos
    inside a verify window truncates at its first occurrence; the
    capacity edge (prompt + budget == n_ctx) stays exact with the
    rejected-tail overshoot routed to the trash page; a speculative=False
    request opts out mid-traffic; zero recompiles after warmup and a
    spec_accept_rate above the 1.0 no-win floor on the repetitive leg."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, speculative=3,
    )
    base = eng.warmup()
    assert {"verify"} <= set(base)
    rng = np.random.default_rng(24)
    prep = np.array([7, 8, 9, 7, 8, 9, 7, 8], np.int32)
    prand = rng.integers(0, 512, size=11).astype(np.int32)
    r1 = eng.submit(prep, max_new_tokens=10)
    r2 = eng.submit(prand, max_new_tokens=7)
    eng.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(r1.result(), _solo(model, params, prep, 10))
    np.testing.assert_array_equal(r2.result(), _solo(model, params, prand, 7))
    assert eng.spec_accept_rate is not None and eng.spec_accept_rate >= 1.0
    # eos truncation inside the verify window.
    want = _solo(model, params, prep, 10)
    eos = int(want[4])
    first = int(np.argmax(want == eos))
    r3 = eng.submit(prep, max_new_tokens=10, eos_id=eos)
    eng.run_until_idle(max_iters=200)
    assert r3.finish_reason == "eos" and r3.tokens == list(want[:first + 1])
    # Capacity edge: the verify window overshoots n_ctx near the end.
    p_edge = rng.integers(0, 512, size=10).astype(np.int32)
    r4 = eng.submit(p_edge, max_new_tokens=54)  # 10 + 54 == n_ctx
    eng.run_until_idle(max_iters=400)
    np.testing.assert_array_equal(
        r4.result(), _solo(model, params, p_edge, 54)
    )
    # Opt-out rides the plain block beside a speculative neighbor.
    r5 = eng.submit(prep, max_new_tokens=10, speculative=False)
    r6 = eng.submit(prand, max_new_tokens=5)
    eng.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(r5.result(), _solo(model, params, prep, 10))
    np.testing.assert_array_equal(r6.result(), _solo(model, params, prand, 5))
    assert eng.compile_stats() == base, "speculative engine recompiled"
    # speculative=True on an unarmed engine fails eagerly.
    plain = ServeEngine(
        model, params, max_slots=1, buckets=[8], decode_block=2,
        page_size=8,
    )
    with pytest.raises(ValueError, match="spec-armed"):
        plain.submit(prep, max_new_tokens=4, speculative=True)


@pytest.mark.slow
def test_mixed_spec_int8_prefix_slot_reuse(model_params, monkeypatch):
    """ISSUE 11 acceptance: all four traffic groups — (fp, int8) x
    (speculative, plain) — INTERLEAVED through one 2-slot paged engine
    with a shared prefix in the mix: every request bit-equal to the solo
    generate() of its numeric path's model, slots and pages reused
    across groups, zero fresh compiles after warmup (compile_stats
    carries verify/verify_q), env arming included."""
    from tpuflow.infer.quant import quantize_model

    model, params = model_params
    qm, qp = quantize_model(model, params, mode="fused_native")
    monkeypatch.setenv("TPUFLOW_SERVE_QUANT", "1")
    monkeypatch.setenv("TPUFLOW_SERVE_SPEC", "3")
    monkeypatch.setenv("TPUFLOW_SERVE_PAGE_SIZE", "8")
    eng = ServeEngine(model, params, max_slots=2, buckets=[8, 16],
                      decode_block=4)
    assert eng.quant_mode == "mxu" and eng.spec_draft == 3
    base = eng.warmup()
    assert {"verify", "verify_q", "prefill_q", "decode_q"} <= set(base)
    rng = np.random.default_rng(25)
    prep = np.array([7, 8, 9, 7, 8, 9, 7], np.int32)
    pa = rng.integers(0, 512, size=5).astype(np.int32)
    pb = rng.integers(0, 512, size=3).astype(np.int32)
    r_fp_spec = eng.submit(prep, max_new_tokens=8)
    r_q_spec = eng.submit(prep, max_new_tokens=8, quantize=True)
    eng.step()
    r_fp_plain = eng.submit(pa, max_new_tokens=6, speculative=False)
    r_q_plain = eng.submit(
        pb, max_new_tokens=6, quantize=True, speculative=False
    )
    eng.run_until_idle(max_iters=300)
    np.testing.assert_array_equal(
        r_fp_spec.result(), _solo(model, params, prep, 8)
    )
    np.testing.assert_array_equal(r_q_spec.result(), _solo(qm, qp, prep, 8))
    np.testing.assert_array_equal(
        r_fp_plain.result(), _solo(model, params, pa, 6)
    )
    np.testing.assert_array_equal(r_q_plain.result(), _solo(qm, qp, pb, 6))
    # Slot + page reuse ACROSS groups: the slots that served fp-spec now
    # serve int8-plain and vice versa; a shared prefix rides along.
    pre = rng.integers(0, 512, size=8).astype(np.int32)  # one full page
    pc = np.concatenate([pre, rng.integers(0, 512, size=2).astype(np.int32)])
    pd = np.concatenate([pre, rng.integers(0, 512, size=4).astype(np.int32)])
    h0 = eng.pool.prefix_hits
    r1 = eng.submit(pc, max_new_tokens=5, quantize=True)
    r2 = eng.submit(pd, max_new_tokens=5, speculative=False)
    eng.run_until_idle(max_iters=300)
    np.testing.assert_array_equal(r1.result(), _solo(qm, qp, pc, 5))
    np.testing.assert_array_equal(r2.result(), _solo(model, params, pd, 5))
    assert eng.pool.prefix_hits > h0  # pd reused pc's prefix page
    assert eng.compile_stats() == base, "mixed-traffic engine recompiled"
    assert eng.live_slots == 0 and eng.pool.allocated_pages == 0


# ------------------------------------ chunked prefill admission boundaries
@pytest.mark.slow
def test_chunked_prefill_admission_boundaries(model_params):
    """Satellite: chunked prefill feeding admission at the boundary
    cases — prompt length exactly ON a chunk boundary, one off either
    side, chunk wider than the bucket (normalizes to one-shot), with the
    bucket's pad_lens in play — all token-exact vs solo generate(), and
    bucket REUSE across distinct lengths adds zero prefill compiles."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[16], decode_block=4,
        prefill_chunk=5,
    )
    eng.warmup()
    base = eng.compile_stats()
    assert base["prefill"] == 1  # one bucket = one prefill program
    rng = np.random.default_rng(3)
    # Bucket width 16, chunk 5: lens around the 5/10/15 boundaries and
    # the full-bucket width (pad 0 — chunk count 16/5 -> 4 chunks).
    for L in (4, 5, 6, 9, 10, 11, 15, 16, 1):
        p = rng.integers(0, 512, size=L).astype(np.int32)
        r = eng.submit(p, max_new_tokens=6)
        eng.run_until_idle(max_iters=100)
        np.testing.assert_array_equal(
            r.result(), _solo(model, params, p, 6)
        )
    # Nine distinct lengths, one bucket: NO fresh compiles (the bucket
    # ladder is the whole prefill compile set).
    assert eng.compile_stats() == base
    # chunk >= bucket width normalizes to a single-pass prefill (same
    # program identity rule as normalize_prefill_chunk): still exact.
    eng2 = ServeEngine(
        model, params, max_slots=1, buckets=[8], decode_block=4,
        prefill_chunk=64,
    )
    p = rng.integers(0, 512, size=7).astype(np.int32)
    r = eng2.submit(p, max_new_tokens=5)
    eng2.run_until_idle(max_iters=100)
    np.testing.assert_array_equal(r.result(), _solo(model, params, p, 5))
    assert eng2.compile_stats()["prefill"] == 1


# ----------------------------------------------- predictor engine routing
@pytest.mark.slow
def test_generation_predictor_routes_through_engine(model_params, monkeypatch):
    """Satellite: a greedy GenerationPredictor stream routes through the
    shared engine from the SECOND batch on (eval flows stop paying one
    compile per batch shape) with byte-identical outputs; TPUFLOW_SERVE=0
    keeps the legacy path."""
    from tpuflow.infer import GenerationPredictor

    model, params = model_params
    rng = np.random.default_rng(4)
    batches = [
        {"tokens": [rng.integers(0, 512, size=L).tolist()
                    for L in (3, 6, 4)]},
        {"tokens": [rng.integers(0, 512, size=L).tolist()
                    for L in (9, 2, 5)]},
        {"tokens": [rng.integers(0, 512, size=L).tolist()
                    for L in (7, 7, 7)]},
    ]
    monkeypatch.delenv("TPUFLOW_SERVE", raising=False)
    routed = GenerationPredictor(model, params, max_new_tokens=6)
    got = [routed(b)["generated"] for b in batches]
    assert routed._serve_engine is not None  # batches 2+ took the engine
    monkeypatch.setenv("TPUFLOW_SERVE", "0")
    legacy = GenerationPredictor(model, params, max_new_tokens=6)
    want = [legacy(b)["generated"] for b in batches]
    assert legacy._serve_engine is None
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # eos + pad assembly honors generate()'s contract through the engine:
    # eos emitted, later positions frozen to pad_id.
    eos = int(want[1][0][2])
    monkeypatch.delenv("TPUFLOW_SERVE", raising=False)
    routed_eos = GenerationPredictor(
        model, params, max_new_tokens=6, eos_id=eos, pad_id=0
    )
    legacy_out = legacy_eos = None
    monkeypatch.setenv("TPUFLOW_SERVE", "0")
    legacy_eos = GenerationPredictor(
        model, params, max_new_tokens=6, eos_id=eos, pad_id=0
    )
    monkeypatch.delenv("TPUFLOW_SERVE", raising=False)
    for b in batches[:2]:
        routed_out = routed_eos(b)["generated"]
        monkeypatch.setenv("TPUFLOW_SERVE", "0")
        legacy_out = legacy_eos(b)["generated"]
        monkeypatch.delenv("TPUFLOW_SERVE", raising=False)
        np.testing.assert_array_equal(routed_out, legacy_out)


# ------------------------------------------------------ serving loop (gang)
@pytest.mark.slow
def test_serve_forever_heartbeats_and_preempt_drain(
    model_params, monkeypatch, tmp_path
):
    """The long-lived loop reuses the gang machinery: heartbeat files
    stamp every iteration (the supervisor's stall detector works on a
    serving gang), and a SIGTERM preemption DRAINS — live slots finish,
    nothing new admits, queued requests survive for the requeue."""
    from tpuflow.utils import preempt

    model, params = model_params
    hb = tmp_path / "hb"
    monkeypatch.setenv("TPUFLOW_HEARTBEAT_FILE", str(hb))
    eng = ServeEngine(
        model, params, max_slots=1, buckets=[8], decode_block=2
    )
    eng.warmup()
    rng = np.random.default_rng(5)
    p1 = rng.integers(0, 512, size=4).astype(np.int32)
    p2 = rng.integers(0, 512, size=6).astype(np.int32)
    r1 = eng.submit(p1, max_new_tokens=8)
    eng.step()  # r1 admitted into the only slot
    r2 = eng.submit(p2, max_new_tokens=4)  # waits for the slot
    preempt.clear_preemption()
    try:
        preempt.request_preemption()
        serve_forever(eng, max_s=10.0)
        # Drain: the live request finished exactly; the queued one was
        # NOT admitted (it rides the requeue, like a train step's drain).
        assert r1.done
        np.testing.assert_array_equal(
            r1.result(), _solo(model, params, p1, 8)
        )
        assert not r2.done and eng.queue_depth == 1
        # Queued-then-drained under SIGTERM (ISSUE 13): the queued
        # request's trace reaches exactly one terminal event — drained
        # — while the completed one's terminal is complete.
        assert r1.terminal_phase == "complete"
        assert r2.terminal_phase == "drained"
        assert sum(
            1 for t in r2.trace if t["phase"] in ("complete", "drained")
        ) == 1
        assert hb.exists()  # at least one iteration stamped the heartbeat
    finally:
        preempt.clear_preemption()
    # Cleared flag: the loop admits + completes the queued request and
    # returns at the deadline (bounded test run).
    serve_forever(eng, max_s=5.0, should_stop=lambda: r2.done)
    assert r2.done
    np.testing.assert_array_equal(r2.result(), _solo(model, params, p2, 4))


# ------------------------------------------------------------- acceptance
@pytest.mark.slow
def test_acceptance_staggered_unequal_requests_beat_sequential(
    model_params
):
    """ISSUE 8 acceptance: >= 8 concurrent requests with staggered
    arrivals, unequal prompt lengths AND unequal budgets through the
    engine on CPU — every request's greedy tokens identical to a solo
    generate() of its prompt, aggregate tokens/s beats the sequential
    baseline (both sides pay their real startup: the engine its bounded
    warmup, the baseline one compile per distinct prompt shape — the
    tentpole's compile-set claim), and the engine never recompiles
    after warmup."""
    # A vocab this file doesn't use elsewhere: the solo-generate programs
    # must be COLD inside the timed baseline window (jit caches are
    # process-global), or the comparison silently warms.
    cfg = GPT2Config.small_test(n_ctx=128, dropout=0.0, vocab_size=499)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(6)
    R = 8
    lens = [5, 14, 23, 9, 31, 47, 3, 18]  # 8 distinct shapes
    budgets = [12, 7, 16, 9, 5, 11, 16, 8]  # unequal decode budgets
    prompts = [
        rng.integers(0, 499, size=L).astype(np.int32) for L in lens
    ]
    gaps = rng.exponential(0.01, size=R)
    gaps[0] = 0.0
    arrive = np.cumsum(gaps)

    t0 = time.monotonic()
    engine = ServeEngine(
        model, params, max_slots=4, buckets=[8, 16, 32, 48],
        decode_block=4,
    )
    base = engine.warmup()
    handles, i = [], 0
    while i < R or engine.live_slots or engine.queue_depth:
        now = time.monotonic() - t0
        while i < R and arrive[i] <= now:
            handles.append(
                engine.submit(prompts[i], max_new_tokens=budgets[i])
            )
            i += 1
        if not engine.step() and i < R:
            time.sleep(0.0005)
    wall_e = time.monotonic() - t0  # warmup included: real server start
    tok_e = sum(len(h.tokens) for h in handles)
    assert engine.compile_stats() == base, "recompiled after warmup"
    # >= 8 requests were genuinely CONCURRENT (slots shared).
    assert max(len(h.tokens) for h in handles) == max(budgets)

    # Sequential baseline with the same arrival schedule; its outputs
    # double as the exactness references.
    t0 = time.monotonic()
    tok_s = 0
    solos = []
    for k in range(R):
        while time.monotonic() - t0 < arrive[k]:
            time.sleep(0.0002)
        out = _solo(model, params, prompts[k], budgets[k])
        solos.append(out)
        tok_s += out.size
    wall_s = time.monotonic() - t0

    for h, want in zip(handles, solos):
        np.testing.assert_array_equal(h.result(), want)
        assert h.done and h.ttft_s is not None
    assert tok_e == tok_s
    agg_e = tok_e / wall_e
    agg_s = tok_s / wall_s
    assert agg_e > agg_s, (
        f"engine {agg_e:.1f} tok/s did not beat sequential "
        f"{agg_s:.1f} tok/s"
    )


# ------------------------------------------------- per-request int8 (ISSUE 9)
def test_resolve_serve_quant_env(monkeypatch):
    from tpuflow.infer.serve import resolve_serve_quant

    monkeypatch.delenv("TPUFLOW_SERVE_QUANT", raising=False)
    assert resolve_serve_quant() is None
    for off in ("0", "false", "off", ""):
        monkeypatch.setenv("TPUFLOW_SERVE_QUANT", off)
        assert resolve_serve_quant() is None
    for on in ("1", "true", "fused_native", "mxu"):
        monkeypatch.setenv("TPUFLOW_SERVE_QUANT", on)
        assert resolve_serve_quant() == "mxu"
    monkeypatch.setenv("TPUFLOW_SERVE_QUANT", "weight_only")
    assert resolve_serve_quant() == "weight"
    # Malformed env arms fused-native loudly (the operator asked for
    # int8; silently serving fp would falsify capacity planning) — but
    # an explicit bad ctor arg is a programming error and raises.
    monkeypatch.setenv("TPUFLOW_SERVE_QUANT", "int7")
    assert resolve_serve_quant() == "mxu"
    with pytest.raises(ValueError, match="unknown quantization mode"):
        resolve_serve_quant("int7")
    assert resolve_serve_quant(True) == "mxu"
    assert resolve_serve_quant(False) is None


def test_submit_quantize_needs_armed_engine(engine):
    with pytest.raises(ValueError, match="quant-armed"):
        engine.submit([1, 2, 3], max_new_tokens=4, quantize=True)


@pytest.fixture(scope="module")
def qengine(model_params):
    """One warmed quant-armed 2-slot engine shared by the int8 serve
    tests (sharing IS the contract — the int8 programs compile once at
    warmup and never again). Consumers are slow-marked (the int8
    program pair costs real compile time; tier-1's 870 s budget is the
    binding constraint — ISSUE 9 duration-guard satellite), so this
    fixture never instantiates in a 'not slow' session."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8], decode_block=4,
        quant="fused_native",
    )
    eng.warmup()
    return eng


@pytest.mark.slow
def test_mixed_fp_int8_requests_share_engine_token_exact(
    qengine, model_params
):
    """The ISSUE 9 serving contract: fp and int8 requests
    INTERLEAVED through one engine — every int8 request's greedy tokens
    bit-equal a solo generate() of the quantized model, every fp request
    bit-equal the fp solo, the two groups never corrupt each other's
    slots, and zero programs compile after warmup (the never-recompile
    contract extends to the quantized programs: compile_stats carries
    prefill_q/decode_q)."""
    from tpuflow.infer.quant import quantize_model

    model, params = model_params
    qm, qp = quantize_model(model, params, mode="fused_native")
    base = qengine.compile_stats()
    assert {"prefill_q", "decode_q"} <= set(base)
    rng = np.random.default_rng(7)
    p_a = rng.integers(0, 512, size=5).astype(np.int32)
    p_b = rng.integers(0, 512, size=3).astype(np.int32)
    # fp and int8 of the SAME prompt side by side (junk-neighbor lite):
    # each group's decode block runs with the other masked out, over the
    # one shared cache.
    r_fp = qengine.submit(p_a, max_new_tokens=6)
    r_q1 = qengine.submit(p_a, max_new_tokens=6, quantize=True)
    qengine.step()  # both admitted, first mixed decode blocks
    r_q2 = qengine.submit(p_b, max_new_tokens=4, quantize=True)  # mid-flight
    qengine.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(
        r_fp.result(), _solo(model, params, p_a, 6)
    )
    np.testing.assert_array_equal(r_q1.result(), _solo(qm, qp, p_a, 6))
    np.testing.assert_array_equal(r_q2.result(), _solo(qm, qp, p_b, 4))
    assert r_q1.quantize and not r_fp.quantize
    # Slot REUSE across numeric paths: the slot that served fp now
    # serves int8 (and vice versa), tokens still exact.
    r_q3 = qengine.submit(p_a, max_new_tokens=4, quantize=True)
    r_fp2 = qengine.submit(p_b, max_new_tokens=4)
    qengine.run_until_idle(max_iters=200)
    np.testing.assert_array_equal(r_q3.result(), _solo(qm, qp, p_a, 4))
    np.testing.assert_array_equal(
        r_fp2.result(), _solo(model, params, p_b, 4)
    )
    assert qengine.compile_stats() == base, "recompiled after warmup"
    assert qengine.live_slots == 0 and qengine.queue_depth == 0


@pytest.mark.slow
def test_int8_parity_suite_reuse_junk_neighbors_eos_env(model_params,
                                                        monkeypatch):
    """ISSUE 9 acceptance (slow tier), mirroring the PR 8 exactness
    suite on the int8 path: an env-armed engine (TPUFLOW_SERVE_QUANT=1)
    decodes int8 requests bit-equal to solo generate() of the quantized
    model across junk neighbor slots, slot reuse, eos early-exit,
    max_new=1-at-admission, and mid-decode admission — with zero fresh
    compiles after warmup and serve.quant_requests accounting."""
    from tpuflow.infer.quant import quantize_model

    model, params = model_params
    qm, qp = quantize_model(model, params, mode="fused_native")
    monkeypatch.setenv("TPUFLOW_SERVE_QUANT", "1")
    eng = ServeEngine(model, params, max_slots=2, buckets=[8, 16],
                      decode_block=4)
    assert eng.quant_mode == "mxu"
    base = eng.warmup()
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 512, size=L).astype(np.int32)
               for L in (3, 8, 11, 6)]
    # Unequal lengths through 2 slots: admissions wait on evictions,
    # slots are REUSED, and fp junk occupies the neighbor slot while
    # int8 requests decode (and vice versa).
    reqs = [
        eng.submit(p, max_new_tokens=7, quantize=(i % 2 == 0))
        for i, p in enumerate(prompts)
    ]
    eng.run_until_idle(max_iters=300)
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        want = (_solo(qm, qp, p, 7) if i % 2 == 0
                else _solo(model, params, p, 7))
        np.testing.assert_array_equal(r.result(), want)
        assert r.finish_reason == "budget"
    # eos early-exit on the int8 path: the eos token itself is emitted,
    # the slot frees at its FIRST occurrence.
    want = _solo(qm, qp, prompts[0], 7)
    eos = int(want[3])
    first = int(np.argmax(want == eos))
    r = eng.submit(prompts[0], max_new_tokens=7, eos_id=eos, quantize=True)
    eng.run_until_idle(max_iters=300)
    assert r.finish_reason == "eos" and r.tokens == list(want[:first + 1])
    # max_new_tokens=1 completes at admission through the int8 prefill.
    r1 = eng.submit(prompts[1], max_new_tokens=1, quantize=True)
    eng.run_until_idle(max_iters=10)
    assert r1.done
    assert r1.tokens == [int(_solo(qm, qp, prompts[1], 1)[0])]
    # Mid-decode admission: an int8 request admitted while fp decodes.
    r_fp = eng.submit(prompts[2], max_new_tokens=9)
    eng.step()
    r_q = eng.submit(prompts[3], max_new_tokens=5, quantize=True)
    eng.run_until_idle(max_iters=300)
    np.testing.assert_array_equal(
        r_fp.result(), _solo(model, params, prompts[2], 9)
    )
    np.testing.assert_array_equal(r_q.result(), _solo(qm, qp, prompts[3], 5))
    assert eng.compile_stats() == base, "recompiled after warmup"
    # generate_many passthrough.
    outs = eng.generate_many(
        prompts[:2], max_new_tokens=3, quantize=True
    )
    for p, toks in zip(prompts[:2], outs):
        np.testing.assert_array_equal(toks, _solo(qm, qp, p, 3))
    assert eng.compile_stats() == base


# ------------------------------------------------ device observatory
@pytest.mark.slow
def test_device_observatory_acceptance(engine, model_params, tmp_path):
    """ISSUE 15 acceptance on the shared warmed engine: (1)
    programs.json covers every program named by compile_stats() with
    compile-time + cost/memory entries (CPU reports both analyses);
    (2) the static budget check records absent ratio keys off-TPU and
    never crashes; (3) a full serve pass with the device observatory
    armed leaves compile_stats() bitwise unchanged (AOT ledger
    collection never touches the jit dispatch cache); (4) the
    device-summary CLI reproduces the ledger jax-free from the run dir
    alone."""
    import json as _json

    from tpuflow import obs
    from tpuflow.obs.__main__ import main as obs_main

    model, params = model_params
    run_dir = tmp_path / "run"
    obs.configure(str(run_dir / "obs"), proc=0)
    try:
        base = engine.compile_stats()
        ledger = engine.collect_program_ledger(
            path=str(run_dir / "obs" / "programs.json")
        )
        names = [e["name"] for e in ledger.programs]
        # Every compile_stats program appears (bucketed prefills as
        # name@width entries), with compile wall + both analyses.
        for key in base:
            assert any(
                n == key or n.split("@")[0] == key for n in names
            ), f"ledger missing {key}: {names}"
        by_name = {e["name"]: e for e in ledger.programs}
        # One decode entry per rung pair of the ladder, rows x positions.
        assert {n for n in names if n.startswith("decode")} == {
            "decode@1x64", "decode@2x64"
        }
        decode = by_name["decode@2x64"]
        assert decode["compile_s"] >= 0
        assert decode["flops"] > 0 and decode["bytes_accessed"] > 0
        assert decode["argument_bytes"] > 0  # CPU memory_analysis works
        assert "temp_bytes" in decode
        # Budget off-TPU: resident bytes recorded, ratio keys absent.
        assert ledger.budget["resident_bytes"] > 0
        assert "over" not in ledger.budget
        # Ledger collection is invisible to the dispatch cache.
        assert engine.compile_stats() == base
        # Serve real traffic with the observatory armed: exactness and
        # the never-recompile contract both hold.
        prompt = np.arange(1, 7, dtype=np.int32)
        h = engine.submit(prompt, max_new_tokens=5)
        engine.run_until_idle(max_iters=300)
        np.testing.assert_array_equal(
            h.result(), _solo(model, params, prompt, 5)
        )
        assert engine.compile_stats() == base
        obs.flush()
    finally:
        obs.configure(None)
    # device-summary reproduces the ledger jax-free from files alone
    # (stdout captured by hand — no capsys beside the shared fixture).
    import io
    import sys as _sys

    buf = io.StringIO()
    old = _sys.stdout
    _sys.stdout = buf
    try:
        assert obs_main(["device-summary", str(run_dir), "--json"]) == 0
    finally:
        _sys.stdout = old
    payload = _json.loads(buf.getvalue())
    assert {p["name"] for p in payload["programs"]} == set(names)
    assert payload["budget"]["resident_bytes"] == ledger.budget[
        "resident_bytes"
    ]
