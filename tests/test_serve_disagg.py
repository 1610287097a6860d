"""Disaggregated prefill/decode + the tiered prefix cache (ISSUE 19).

The load-bearing contracts, in the fast tier on shared warmed engines
(ZERO fresh compiles per case — the never-recompile contract extends to
imports and promotions):

- **Cross-engine ship is bit-equal.** A prefill-role engine exports a
  prompt's KV pages through the store; a decode-role engine imports by
  key and decodes EXACTLY the solo ``generate()`` tokens, with zero
  prefill calls on the decode engine and ``compile_stats()`` unchanged
  on both.
- **Suffix resume.** A longer prompt whose digest chain extends a
  committed set imports the covered pages and prefills only the suffix.
- **Torn sets fall back.** A corrupted blob never loads; the request
  admits through classic local prefill, bit-equal, with the
  ``kv_fallback`` trace phase as evidence.
- **Tier promotion is exact.** Pages evicted to the host tier promote
  back on re-admission instead of recomputing (zero extra prefill
  calls), bit-equal, compile-stable.

The heavy matrix (fp/int8 × spec/plain × page-boundary lengths), the
prefill-worker-dies chaos drive, and disk-tier restart survival are
slow-marked below.
"""

import json as _json
import os
import shutil
import threading
import time
import urllib.error as _uerr
import urllib.request as _ureq

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuflow.infer import generate
from tpuflow.infer.serve import ServeEngine, resolve_serve_role
from tpuflow.models.gpt2 import GPT2, GPT2Config


def _model_params(**kw):
    cfg = GPT2Config.small_test(n_ctx=64, dropout=0.0, **kw)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def model_params():
    return _model_params()


@pytest.fixture(scope="module")
def store_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("kvstore"))


def _ship_pair(model_params, store_dir):
    model, params = model_params
    pf = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, role="prefill", kv_store_dir=store_dir,
    )
    pf.warmup()
    dc = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, role="decode", kv_store_dir=store_dir,
    )
    dc.warmup()
    return pf, dc


@pytest.fixture(scope="module")
def ship_pair(model_params, store_dir):
    """One warmed prefill-role + one warmed decode-role engine sharing
    a KV store — the disaggregated topology, in-process. Shared by the
    fast ship tests; compile baselines are pinned per test."""
    return _ship_pair(model_params, store_dir)


@pytest.fixture(scope="module")
def scan_shipping(tmp_path_factory):
    """The same pair under ``scan_layers``: pages are exported from and
    imported into the layer-stacked pool (ISSUE 27)."""
    model_params = _model_params(scan_layers=True)
    return model_params, _ship_pair(
        model_params, str(tmp_path_factory.mktemp("kvstore_scan"))
    )


@pytest.fixture(params=["blocks", "scan"])
def shipping(request):
    """``((model, params), (prefill engine, decode engine))`` for each
    paged cache layout."""
    if request.param == "scan":
        return request.getfixturevalue("scan_shipping")
    return (
        request.getfixturevalue("model_params"),
        request.getfixturevalue("ship_pair"),
    )


def _solo(model, params, prompt, n_new):
    return np.asarray(
        generate(
            model, params, np.asarray(prompt, np.int32)[None, :],
            max_new_tokens=n_new, temperature=0.0,
        )
    )[0]


def _drive(engine, handle):
    engine.run_until_idle(max_iters=400)
    assert handle.done
    return [int(t) for t in handle.tokens]


def _admitted(handle) -> dict:
    return next(t for t in handle.trace if t["phase"] == "admitted")


# ------------------------------------------------------------ role knob
def test_resolve_serve_role(monkeypatch, capsys):
    assert resolve_serve_role() == "both"
    assert resolve_serve_role("Prefill") == "prefill"
    assert resolve_serve_role("decode") == "decode"
    with pytest.raises(ValueError):
        resolve_serve_role("router")
    # A malformed ENV degrades with a warning instead of refusing to
    # serve — the bucket-knob idiom split by blast radius.
    monkeypatch.setenv("TPUFLOW_SERVE_ROLE", "decoder")
    assert resolve_serve_role() == "both"
    assert "TPUFLOW_SERVE_ROLE" in capsys.readouterr().out
    monkeypatch.setenv("TPUFLOW_SERVE_ROLE", "prefill")
    assert resolve_serve_role() == "prefill"


# ----------------------------------------------------------- fast: ship
def test_ship_roundtrip_bit_equal_zero_decode_prefill(shipping):
    """The tentpole roundtrip: prefill engine ships, decode engine
    imports, tokens are bit-equal to solo generate(), the decode engine
    never ran a prefill, and neither engine compiled anything new."""
    (model, params), (pf, dc) = shipping
    pf_base, dc_base = pf.compile_stats(), dc.compile_stats()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 512, size=9).astype(np.int32)
    want = _solo(model, params, prompt, 6).tolist()

    key = pf.ship(prompt)
    assert pf.kv_store.contains(key)
    dc_prefills = dc._prefill_calls
    h = dc.submit(prompt, max_new_tokens=6, kv_key=key)
    assert h.kv_import is not None
    got = _drive(dc, h)
    assert got == want
    assert h.finish_reason == "budget"
    assert dc._prefill_calls == dc_prefills  # zero local prefill
    assert _admitted(h)["prefilled"] == "ship"
    assert pf.compile_stats() == pf_base
    assert dc.compile_stats() == dc_base


def test_a_set_shipped_by_an_older_build_imports_and_a_new_ship_equals_it(
    shipping,
):
    """Peers of two builds still talk (ISSUE 35): a set that PR 34's
    build shipped, pages ``(k, ..., page_size, H, D)`` out of a pool
    whose leaves ended so, admits its prompt on this build's decode
    engine, whose pool keeps a token's heads as one vector, with no
    prefill and solo ``generate()``'s tokens; and what this build's
    prefill engine ships for the same prompt is the same bytes."""
    (model, params), (pf, dc) = shipping
    name = "gpt2-scan" if model.config.scan_layers else "gpt2-blocks"
    src = os.path.join(os.path.dirname(__file__), "data", "kv_sets_pr34", name)
    for f in os.listdir(src):  # one set: its blob and its manifest
        shutil.copy(os.path.join(src, f), dc.kv_store.root)
    key = "37e7333b3db0156f3617edd75ed3594cd8388fb8"
    old = dc.kv_store.load(key)
    assert {a.shape[-3:] for a in old.pages.values()} == {(8, 4, 32)}
    dc_base, dc_prefills = dc.compile_stats(), dc._prefill_calls
    h = dc.submit(old.prompt, max_new_tokens=6, kv_key=key)
    assert _drive(dc, h) == _solo(model, params, old.prompt, 6).tolist()
    assert dc._prefill_calls == dc_prefills
    assert _admitted(h)["prefilled"] == "ship"
    assert dc.compile_stats() == dc_base
    new = pf.prefill_export(old.prompt)
    assert new.tok0 == old.tok0 and list(new.pages) == list(old.pages)
    for leaf, pages in old.pages.items():
        np.testing.assert_array_equal(new.pages[leaf], pages)


def test_ship_suffix_resume_prefills_only_the_suffix(
    model_params, ship_pair
):
    """A prompt EXTENDING a committed one imports the covered pages and
    chunk-prefills only its suffix — still bit-equal, still
    compile-stable."""
    model, params = model_params
    pf, dc = ship_pair
    dc_base = dc.compile_stats()
    rng = np.random.default_rng(4)
    base = rng.integers(0, 512, size=8).astype(np.int32)  # 1 full page
    ext = np.concatenate(
        [base, rng.integers(0, 512, size=3).astype(np.int32)]
    )
    want = _solo(model, params, ext, 5).tolist()

    key = pf.ship(base)
    before = dc._prefill_calls
    h = dc.submit(ext, max_new_tokens=5, kv_key=key)
    assert h.kv_import is not None  # chain-prefix match accepted
    got = _drive(dc, h)
    assert got == want
    # The suffix still prefilled (once) — but the base page came from
    # the shipped set, not recomputation.
    assert dc._prefill_calls == before + 1
    assert _admitted(h).get("shipped_pages", 0) >= 1
    assert dc.compile_stats() == dc_base


def test_torn_shipped_set_falls_back_to_local_prefill(
    model_params, ship_pair
):
    """Corrupt the committed blob: the import returns None (never
    raises, never partial), the request admits through classic local
    prefill, the answer stays bit-equal, and the ``kv_fallback`` trace
    phase records the degradation."""
    model, params = model_params
    pf, dc = ship_pair
    dc_base = dc.compile_stats()
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 512, size=12).astype(np.int32)
    want = _solo(model, params, prompt, 5).tolist()

    key = pf.ship(prompt)
    blob = pf.kv_store._blob(key)
    with open(blob, "rb") as f:
        data = bytearray(f.read())
    data[len(data) // 2] ^= 0xFF
    with open(blob, "wb") as f:
        f.write(bytes(data))

    before = dc._prefill_calls
    h = dc.submit(prompt, max_new_tokens=5, kv_key=key)
    assert h.kv_import is None
    got = _drive(dc, h)
    assert got == want
    assert dc._prefill_calls == before + 1  # the local fallback
    assert any(t["phase"] == "kv_fallback" for t in h.trace)
    assert dc.compile_stats() == dc_base


def test_ship_requires_a_store(model_params):
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=1, buckets=[8], decode_block=2,
        page_size=8,
    )
    assert eng.kv_store is None
    with pytest.raises(ValueError):
        eng.ship(np.arange(1, 9, dtype=np.int32))


def test_unknown_kv_key_is_a_clean_fallback(model_params, ship_pair):
    model, params = model_params
    _, dc = ship_pair
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 512, size=7).astype(np.int32)
    want = _solo(model, params, prompt, 4).tolist()
    h = dc.submit(prompt, max_new_tokens=4, kv_key="no-such-key")
    assert h.kv_import is None
    assert _drive(dc, h) == want


# ----------------------------------------------------- fast: tier cache
def test_tier_promotion_readmits_without_prefill(model_params):
    """Evict a hot prompt's pages into the host tier via pool pressure,
    re-admit it: pages promote back (tier-hit counters as evidence),
    prefill does NOT rerun, tokens are bit-equal, and nothing
    recompiled."""
    model, params = model_params
    eng = ServeEngine(
        model, params, max_slots=1, buckets=[16, 32], decode_block=4,
        page_size=8, n_pages=9,
        kv_host_mb=8.0,
    )
    eng.warmup()
    base = eng.compile_stats()
    rng = np.random.default_rng(7)
    # 2 full pages + 1 token: a tier-covered re-admit is feed-eligible
    # (covered*ps >= L-1) and skips prefill entirely.
    hot = rng.integers(0, 512, size=17).astype(np.int32)
    want = _solo(model, params, hot, 5).tolist()

    h = eng.submit(hot, max_new_tokens=5)
    assert _drive(eng, h) == want
    # Churn unrelated prompts through the 9-page pool until the hot
    # pages are evicted — evictions now SPILL instead of forget.
    for _ in range(6):
        p = rng.integers(0, 512, size=int(rng.integers(9, 16)))
        hc = eng.submit(p.astype(np.int32), max_new_tokens=4)
        _drive(eng, hc)
    tier = eng.pool.tier
    assert tier.pages_host > 0 and eng.pool.evictions > 0

    prefills = eng._prefill_calls
    hits0 = tier.hits_host
    h2 = eng.submit(hot, max_new_tokens=5)
    assert _drive(eng, h2) == want  # promotion is exact
    assert eng._prefill_calls == prefills  # no recompute
    assert tier.hits_host >= hits0 + 2  # both full pages promoted
    assert eng.pool.tier_hits >= 2
    assert eng.compile_stats() == base


# ------------------------------------------------------------ slow tier
@pytest.mark.slow
def test_ship_matrix_quant_spec_page_boundaries(model_params, tmp_path):
    """fp/int8 × spec/plain × L∈{ps-1, ps, ps+1}: every cell decodes a
    SHIPPED admission bit-equal to its solo reference (fp vs the
    int8-quantized model) with zero decode-engine prefills and stable
    compile stats, on one quant+spec-armed prefill/decode pair."""
    from tpuflow.infer.quant import quantize_model

    model, params = model_params
    qm, qp = quantize_model(model, params, mode="fused_native")
    store = str(tmp_path / "kv")
    pf = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, role="prefill", kv_store_dir=store,
        quant=True,
    )
    pf.warmup()
    dc = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, role="decode", kv_store_dir=store,
        quant=True, speculative=2,
    )
    base = dc.warmup()
    rng = np.random.default_rng(8)
    M = 6
    for L in (7, 8, 9):
        prompt = rng.integers(0, 512, size=L).astype(np.int32)
        refs = {
            False: _solo(model, params, prompt, M).tolist(),
            True: _solo(qm, qp, prompt, M).tolist(),
        }
        for quant in (False, True):
            key = pf.ship(prompt, quantize=quant)
            for spec in (False, True):
                before = dc._prefill_calls
                h = dc.submit(
                    prompt, max_new_tokens=M,
                    kv_key=key, quantize=quant, speculative=spec,
                )
                assert h.kv_import is not None, (L, quant, spec)
                got = _drive(dc, h)
                assert got == refs[quant], (L, quant, spec)
                assert dc._prefill_calls == before, (L, quant, spec)
    assert dc.compile_stats() == base


@pytest.mark.slow
def test_quant_mismatched_import_is_rejected(model_params, tmp_path):
    """A page set shipped under fp must NOT import into an int8-decode
    admission (the KV numerics differ) — the meta gate rejects it and
    the quant request falls back to local prefill, bit-equal."""
    from tpuflow.infer.quant import quantize_model

    model, params = model_params
    qm, qp = quantize_model(model, params, mode="fused_native")
    store = str(tmp_path / "kv")
    pf = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, role="prefill", kv_store_dir=store,
    )
    pf.warmup()
    dc = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4,
        page_size=8, role="decode", kv_store_dir=store, quant=True,
    )
    dc.warmup()
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 512, size=9).astype(np.int32)
    want = _solo(qm, qp, prompt, 5).tolist()
    key = pf.ship(prompt)  # fp pages
    h = dc.submit(prompt, max_new_tokens=5, kv_key=key, quantize=True)
    assert h.kv_import is None  # meta gate: quant mismatch
    assert _drive(dc, h) == want


@pytest.mark.slow
def test_disk_tier_survives_engine_restart(model_params, tmp_path):
    """Disk-only tier: evicted hot pages land in the node-local disk
    store; a FRESH engine over the same dir rescans them at init and a
    re-admit promotes from disk with zero prefill calls — the
    hot-prompts-survive-replica-restarts claim, engine-level."""
    model, params = model_params
    disk = str(tmp_path / "tier")
    rng = np.random.default_rng(10)
    hot = rng.integers(0, 512, size=17).astype(np.int32)
    want = _solo(model, params, hot, 5).tolist()

    def build():
        eng = ServeEngine(
            model, params, max_slots=1, buckets=[16, 32],
            decode_block=4, page_size=8, n_pages=9,
            kv_disk_dir=disk,
        )
        eng.warmup()
        return eng

    from tpuflow.infer import kv_store as _kvstore

    hot_digests = _kvstore.chain_digests(hot, 8)
    assert len(hot_digests) == 2

    eng = build()
    h = eng.submit(hot, max_new_tokens=5)
    assert _drive(eng, h) == want
    # Churn until BOTH hot pages are provably on disk — pool pressure
    # alone decides eviction order, so bound the loop generously.
    for _ in range(12):
        p = rng.integers(0, 512, size=int(rng.integers(9, 16)))
        _drive(eng, eng.submit(p.astype(np.int32), max_new_tokens=4))
        if all(
            eng.pool.tier.locate(d) == "disk" for d in hot_digests
        ):
            break
    assert all(
        eng.pool.tier.locate(d) == "disk" for d in hot_digests
    )

    reborn = build()  # the restart: fresh pool, fresh jit cache
    assert reborn.pool.tier.pages_disk >= 2  # rescan found the pages
    base = reborn.compile_stats()
    prefills = reborn._prefill_calls
    h2 = reborn.submit(hot, max_new_tokens=5)
    assert _drive(reborn, h2) == want
    assert reborn._prefill_calls == prefills
    assert reborn.pool.tier.hits_disk >= 2
    assert reborn.compile_stats() == base


@pytest.mark.slow
def test_chaos_prefill_worker_dies_mid_ship(tmp_path, monkeypatch):
    """THE disaggregated chaos drive, end to end over real sockets:
    1 prefill + 2 decode replicas behind the phase-aware router and a
    FrontDoor (which mints the trace contexts), Poisson load, the
    prefill worker killed through the PR 6 ``prefill_kill`` fault
    vocabulary. Asserts: zero drops, every answer bit-equal to solo
    generate(), ships happened while the worker lived and every
    post-kill long prompt fell back to local prefill — proven by the
    router counters AND the ``router.ship`` trace spans (ok=True
    pre-kill, ok=False post-kill) — and no decode replica recompiled."""
    from tpuflow.infer.frontdoor import FrontDoor, http_forward
    from tpuflow.infer.router import FleetBusy, Router
    from tpuflow.obs import fleet as obs_fleet
    from tpuflow.obs import trace as reqtrace
    from tpuflow.testing import faults
    from tpuflow.testing.chaos import (
        LocalReplica,
        apply_replica_plan,
        run_poisson,
    )

    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("TPUFLOW_TRACE_DIR", trace_dir)
    monkeypatch.setenv("TPUFLOW_TRACE", "1")
    monkeypatch.setenv("TPUFLOW_TRACE_SAMPLE", "1.0")

    cfg = GPT2Config.small_test(n_ctx=64, dropout=0.0)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(11)
    R, M = 16, 6
    prompts = [
        rng.integers(0, 512, size=int(L)).astype(np.int32)
        for L in rng.integers(4, 20, size=R)
    ]
    expected = {
        f"dg-{k}": _solo(model, params, p, M).tolist()
        for k, p in enumerate(prompts)
    }
    n_long_post = sum(1 for p in prompts[R // 2:] if len(p) >= 8)
    assert n_long_post >= 1  # the seed must exercise the fallback

    kv_dir = str(tmp_path / "kv")
    reg = str(tmp_path / "fleet")
    dev_lock = threading.Lock()
    replicas: dict[str, LocalReplica] = {}
    baselines: dict[str, dict] = {}
    door = None
    try:
        for rid, role in (
            ("pf-0", "prefill"), ("dc-0", "decode"), ("dc-1", "decode"),
        ):
            eng = ServeEngine(
                model, params, max_slots=2, decode_block=4,
                buckets=[16, 32], page_size=8,
                role=role, kv_store_dir=kv_dir,
            )
            with dev_lock:
                eng.warmup()
            rep = LocalReplica(
                rid, eng, registration_dir=reg, device_lock=dev_lock,
            )
            replicas[rid] = rep
            baselines[rid] = eng.compile_stats()

        obsy = obs_fleet.FleetObservatory(
            reg, timeout_s=0.5, stale_s=10.0, poll_interval_s=0.02,
        )
        router = Router(
            obsy.poll, http_forward,
            page_size=8, timeout_s=3.0, retries=4, backoff_s=0.02,
            queue_timeout_s=60.0, refresh_s=0.02,
            ship_min_tokens=8,
        )
        router.refresh(force=True)
        snap = obsy.poll()
        rows = {r["id"]: r for r in snap["replicas"]}
        assert rows["pf-0"]["serve_role"] == "prefill"
        door = FrontDoor(router, host="127.0.0.1", port=0)

        def submit(req: dict) -> dict:
            post = _ureq.Request(
                door.url + "/generate",
                data=_json.dumps(req).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            try:
                with _ureq.urlopen(post, timeout=90.0) as resp:
                    return _json.loads(resp.read())
            except _uerr.HTTPError as e:
                if e.code == 503:
                    raise FleetBusy(e.read().decode("utf-8", "replace"))
                raise

        def batch(lo: int, hi: int) -> list[dict]:
            return [
                {
                    "id": f"dg-{k}",
                    "prompt": [int(t) for t in prompts[k]],
                    "max_new_tokens": M,
                }
                for k in range(lo, hi)
            ]

        # Warm-path proof first: ships happen while the worker lives.
        results = run_poisson(
            submit, batch(0, R // 2), rate_qps=25.0, rng=rng
        )
        assert [r for r in results if r["outcome"] != "ok"] == []
        ships_live = router.stats()["router_ships"]
        assert ships_live >= 1

        # Kill the prefill worker through the fault vocabulary, then
        # drive the second half: long prompts must fall back.
        faults.reset()
        monkeypatch.setenv("TPUFLOW_FAULT", "prefill_kill:pf-0@0.0")
        plan = faults.replica_plan()
        assert plan == [("prefill_kill", "pf-0", 0.0)]
        chaos = apply_replica_plan(replicas, plan, t0=time.monotonic())
        chaos.join(timeout=10.0)
        fb0 = router.stats()["router_ship_fallbacks"]
        results += run_poisson(
            submit, batch(R // 2, R), rate_qps=25.0, rng=rng
        )

        # ---- zero drops; every answer bit-equal.
        assert [r for r in results if r["outcome"] != "ok"] == []
        for r in results:
            rid = r["request"]["id"]
            assert r["response"]["tokens"] == expected[rid], rid
        stats = router.stats()
        assert stats["router_dropped"] == 0
        # Every post-kill long prompt degraded through the explicit
        # fallback counter — never an error, never a drop.
        assert stats["router_ship_fallbacks"] - fb0 >= n_long_post
        assert stats["router_ships"] == ships_live  # no ship after kill

        # ---- the trace spans prove both modes: a successful ship hop
        # pre-kill, a failed one (local-prefill fallback) post-kill.
        spans = [
            s for s in reqtrace.read_spans(trace_dir)
            if s.get("name") == "router.ship"
        ]
        assert any(s.get("ok") for s in spans)
        assert any(not s.get("ok") for s in spans)

        # ---- no decode replica recompiled under the loss.
        for rid in ("dc-0", "dc-1"):
            assert (
                replicas[rid].engine.compile_stats() == baselines[rid]
            ), f"{rid} recompiled"
    finally:
        if door is not None:
            door.close()
        for rep in replicas.values():
            rep.close()
