"""The bench suite's TPU-gated sub-legs must be *proven executable* on CPU
before a chip run spends its budget on them. These tests drive the same
helper functions the on-TPU legs call, on a tiny model."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from tpuflow.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(
        vocab_size=256, n_ctx=256, n_embd=64, n_layer=2, n_head=2,
        dropout=0.0, dtype=jnp.float32,
    )
    model = GPT2(cfg)
    x = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    return model, params, cfg


def test_natural_prompt_shape_and_content(monkeypatch):
    # Pin the embedded-paragraph path: a developer's data/*.txt corpus
    # (the normal lm_text workflow) must not change what this asserts.
    from tpuflow.data import datasets

    monkeypatch.setattr(datasets, "resolve_text_path", lambda *a, **k: None)
    p = bench._natural_prompt(64, 50257)
    assert p.shape == (1, 64)
    assert p.dtype == np.int32
    # Natural prose, not a tiled pattern: no period-16 repetition.
    assert not np.array_equal(p[0, :16], p[0, 16:32])
    # Byte-level tokens stay inside any LM vocab.
    assert p.min() >= 0 and p.max() < 256


def test_bench_spec_prompt_repetitive(tiny_lm):
    model, params, cfg = tiny_lm
    rep = np.tile(np.arange(16, dtype=np.int32)[None, :], (1, 4))
    rec = bench._bench_spec_prompt(model, params, rep, n_new=24)
    assert rec["numerics_ok"] is True
    assert rec["tokens_per_forward"] >= 1.0
    assert rec["speedup"] > 0
    assert rec["tokens_per_s"] > 0 and rec["plain_tokens_per_s"] > 0


def test_bench_spec_prompt_natural(tiny_lm):
    model, params, cfg = tiny_lm
    nat = bench._natural_prompt(64, cfg.vocab_size)
    rec = bench._bench_spec_prompt(model, params, nat, n_new=24)
    # Honesty contract: correctness always reported; a random-weight
    # model on natural text may accept ~nothing — the rate just has to
    # be present and >= the 1 token/forward floor.
    assert rec["numerics_ok"] is True
    assert rec["tokens_per_forward"] >= 1.0


def test_peak_flops_table_matches_device_kind_strings():
    """The MFU denominator keys on jax.devices()[0].device_kind, which
    reads like 'TPU v5 lite' — not 'v5e'. Pin the lookup against the
    real strings each generation reports (VERDICT r3 weak #6: the table
    had never been exercised against one)."""
    peak_for = bench._peak_flops_for  # the REAL production lookup

    assert peak_for("TPU v5 lite") == 197e12       # v5e chips report this
    assert peak_for("TPU v5litepod") == 197e12     # pod-slice spelling
    assert peak_for("TPU v5p") == 459e12
    assert peak_for("TPU v5") == 459e12
    assert peak_for("TPU v4") == 275e12
    assert peak_for("TPU v6 lite") == 918e12       # Trillium
    assert peak_for("TPU v6e") == 918e12
    # v5 substrings must not shadow the lite entries: order matters.
    lite_idx = next(
        i for i, (k, _) in enumerate(bench._PEAK_FLOPS) if k == "v5 lite"
    )
    v5_idx = next(
        i for i, (k, _) in enumerate(bench._PEAK_FLOPS) if k == "v5"
    )
    assert lite_idx < v5_idx
    # Unknown hardware is an error, not a default peak.
    with pytest.raises(ValueError, match="v9 hyperchip"):
        peak_for("TPU v9 hyperchip")


def test_bench_int8_decode_leg(tiny_lm):
    """The int8 decode sub-leg must be executable (CPU drive: speedup is
    noise here, but the record shape — both sub-legs under the ISSUE 9
    names, the gate verdict, the token-agreement stat, and the fused
    leg's dispatch record — is pinned before real chip time is spent
    on it)."""
    model, params, cfg = tiny_lm
    prompt = np.arange(2 * 12, dtype=np.int32).reshape(2, 12) % cfg.vocab_size
    rec = bench._bench_int8_decode(model, params, prompt, n_new=8)
    assert set(rec) == {"fp_tokens_per_s", "weight_mode_gate",
                        "weight_only", "fused_native"}
    assert rec["fp_tokens_per_s"] > 0
    # A tiny test model sits far below the measured threshold: gated off.
    gate = rec["weight_mode_gate"]
    assert set(gate) == {"apply", "reason"}
    assert gate["apply"] is False
    assert "gated OFF" in gate["reason"]
    for mode in ("weight_only", "fused_native"):
        sub = rec[mode]
        assert sub["tokens_per_s"] > 0 and sub["speedup_vs_fp"] > 0
        assert 0.0 <= sub["token_agreement"] <= 1.0
        assert 0.0 <= sub["greedy_seq_agreement"] <= 1.0
    # The fused leg says which impl each hot decode shape dispatches to
    # on this host (CPU: always the XLA int8 path).
    impl = rec["fused_native"]["impl"]
    assert set(impl) == {"qkv", "mlp", "lm_head"}
    assert all(v in ("xla", "pallas") for v in impl.values())


def test_compact_summary_is_small_and_carries_headline():
    """The LAST stdout line of the main bench: must re-state the metric
    fields (a driver parsing the last JSON line still gets the metric)
    and fit WELL under a ~2,000-char stdout tail. The train headline
    comes from THIS run's on-chip train child or not at all: nothing
    replays an earlier record."""
    import json

    record = {
        "metric": "sharded_ckpt_save_restore_throughput",
        "value": 3.97, "unit": "GB/s", "vs_baseline": 1.985,
        "extra": {
            "tiers": {
                "primary": {"combined_gbps": 3.97},
                "disk": {"combined_gbps": 1.11},
            },
            # A key an older bench attached; must be ignored now.
            "tpu_evidence": {"train": {"platform": "tpu", "mfu": 0.9}},
        },
    }
    s = bench._compact_summary(record, train=None)
    line = json.dumps(s)
    assert len(line) < 800, len(line)
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert s[k] == record[k]
    d = s["summary"]
    assert d["host_combined_gbps"] == 3.97
    assert d["disk_combined_gbps"] == 1.11
    assert "train" not in d and "best_mfu_sweep" not in d
    # A CPU train child carries no device headline either.
    cpu = bench._compact_summary(
        record, train={"platform": "cpu", "mfu": None, "tokens_per_s": 1.0}
    )
    assert "train" not in cpu["summary"]
    s2 = bench._compact_summary(
        record, train={"platform": "tpu", "mfu": 0.5, "tokens_per_s": 1.0}
    )
    assert s2["summary"]["train"] == {
        "platform": "tpu", "mfu": 0.5, "tokens_per_s": 1.0,
    }


def test_flash_crossover_fit():
    """Crossover = smallest trusted T where flash fwd+bwd wins, only when
    every larger measured T agrees; suspect/broken points are excluded."""
    recs = {
        "T512": {"numerics_ok": True, "fwdbwd_speedup": 0.2,
                 "timing_suspect": ["xla"]},
        "T1024": {"numerics_ok": True, "fwdbwd_speedup": 1.1},
        "T2048": {"numerics_ok": True, "fwdbwd_speedup": 1.73},
        "T4096": {"numerics_ok": True, "fwdbwd_speedup": 2.1},
    }
    assert bench._flash_crossover_from(recs) == 1024
    # A numerics failure at a larger T doesn't veto (it carries no
    # speedup at all); a genuine slower point above the candidate does.
    recs["T4096"] = {"numerics_ok": True, "fwdbwd_speedup": 0.9}
    assert bench._flash_crossover_from(recs) is None
    recs["T4096"] = {"numerics_ok": False, "max_err": 1.0}
    assert bench._flash_crossover_from(recs) == 1024
    assert bench._flash_crossover_from({}) is None


def test_flash_tuning_roundtrip(tmp_path, monkeypatch):
    """bench persists the measured crossover where the dispatcher's
    impl='auto' reads it: env var beats file beats default."""
    import importlib

    # tpuflow.ops re-exports the attention FUNCTION; get the module.
    attn = importlib.import_module("tpuflow.ops.attention")

    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path))
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ", raising=False)
    attn._flash_tuning_cache = None  # drop the per-process cache
    assert attn._flash_min_seq() == attn._DEFAULT_FLASH_MIN_SEQ
    bench._persist_flash_tuning(1024)
    attn._flash_tuning_cache = None
    assert attn._flash_min_seq() == 1024
    monkeypatch.setenv("TPUFLOW_FLASH_MIN_SEQ", "512")
    assert attn._flash_min_seq() == 512  # env var wins over the file
    # A malformed env var warns (once) and falls through to the measured
    # tuning file — the host's crossover beats the shipped constant.
    monkeypatch.setenv("TPUFLOW_FLASH_MIN_SEQ", "banana")
    attn._warned_malformed_env = False
    with pytest.warns(UserWarning, match="FLASH_MIN_SEQ"):
        assert attn._flash_min_seq() == 1024
    assert attn._flash_min_seq() == 1024  # warned once, still resolves
    attn._flash_tuning_cache = None
    attn._warned_malformed_env = False


def test_flash_tuning_bwd_key_roundtrip(tmp_path, monkeypatch):
    """ISSUE 10 satellite: the bwd-only crossover persists as
    flash_min_seq_bwd and the dispatcher's training path maxes it
    against the fwd+bwd composition key."""
    import importlib
    import json

    attn = importlib.import_module("tpuflow.ops.attention")
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path))
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ", raising=False)
    bench._persist_flash_tuning(512, 256, 2048)
    with open(attn.flash_tuning_path()) as f:
        rec = json.load(f)
    assert rec["flash_min_seq"] == 512
    assert rec["flash_min_seq_fwd"] == 256
    assert rec["flash_min_seq_bwd"] == 2048
    attn._flash_tuning_cache = None
    # Training path: the measured backward loss region gates dispatch.
    assert attn._flash_min_seq(needs_bwd=True) == 2048
    assert attn._flash_min_seq(needs_bwd=False) == 256
    attn._flash_tuning_cache = None


def test_flash_tuning_not_persisted_on_suspect_sweep(tmp_path, monkeypatch):
    """A jitter-polluted sweep (any timing_suspect point) must not clobber
    the host tuning file — dropping suspect points can only RAISE the
    fitted crossover and would silently disable measured flash wins."""
    import importlib
    import json

    attn = importlib.import_module("tpuflow.ops.attention")
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path))
    bench._persist_flash_tuning(1024)  # a prior clean run's value
    recs = {
        "T2048": {"numerics_ok": True, "fwdbwd_speedup": 0.5,
                  "timing_suspect": ["xla"]},
        "T4096": {"numerics_ok": True, "fwdbwd_speedup": 2.0},
    }
    # Simulate bench_flash's gate: crossover fits 4096, but the sweep is
    # dirty, so the file must keep the prior value.
    assert bench._flash_crossover_from(recs) == 4096
    clean = not any(
        r.get("timing_suspect") for r in recs.values() if isinstance(r, dict)
    )
    assert not clean
    with open(attn.flash_tuning_path()) as f:
        assert json.load(f)["flash_min_seq"] == 1024


def test_mfu_roofline_bounds():
    """The ceiling argument attached to every sweep config: GPT-2-124M on
    v5e is compute-bound at the swept batch sizes (memory floor well
    under the compute floor), so attainable_mfu ~= 1.0 and the measured
    gap is kernel/pipeline inefficiency, not an HBM wall."""
    n = 124_000_000
    r = bench._mfu_roofline(n, 8, 512, peak_flops=197e12, hbm_gbps=819.0)
    assert r["bound"] == "compute"
    assert r["attainable_mfu"] == 1.0
    assert r["compute_floor_ms"] > 3 * r["memory_floor_ms"]
    # Tiny batch flips the balance: one sequence of 32 tokens streams the
    # full optimizer state per step — memory-bound.
    r2 = bench._mfu_roofline(n, 1, 32, peak_flops=197e12, hbm_gbps=819.0)
    assert r2["bound"] == "memory"
    assert r2["attainable_mfu"] < 1.0
    # HBM table matches device_kind strings like the FLOPs table does.
    assert bench._hbm_gbps_for("TPU v5 lite") == 819.0
    assert bench._hbm_gbps_for("TPU v6e") == 1640.0
    with pytest.raises(ValueError, match="TPU weird"):
        bench._hbm_gbps_for("TPU weird")


def test_mfu_roofline_memory_floor_constant():
    """Pin the memory-floor arithmetic to its docstring derivation: bf16
    params read fwd+bwd (2*2N) + bf16 grads write+read (2*2N) + f32 adamw
    mu/nu read+write (2*8N) + f32 params read+write (2*4N) = 32N bytes.
    (A prior revision shipped 28N against this same derivation.)"""
    assert bench._ROOFLINE_HBM_BYTES_PER_PARAM == (
        2 * 2 + 2 * 2 + 2 * 8 + 2 * 4
    ) == 32
    n, hbm = 1_000_000, 819.0
    r = bench._mfu_roofline(n, 8, 512, peak_flops=197e12, hbm_gbps=hbm)
    expect_ms = 32.0 * n / (hbm * 1e9) * 1e3
    assert r["memory_floor_ms"] == round(expect_ms, 3)


def test_measure_device_staging_fields():
    """The ckpt_device leg's transport-split helper must be executable
    (CPU drive) and report positive GB/s + seconds for both directions."""
    import jax
    import numpy as np

    state = {
        "w0": jax.device_put(np.random.default_rng(0).standard_normal(
            (256, 1024)).astype(np.float32)),
        "w1": jax.device_put(np.zeros((128, 1024), np.float32)),
    }
    nbytes = sum(v.nbytes for v in state.values())
    rec = bench.measure_device_staging(state, nbytes)
    assert set(rec) == {"stage_get_gbps", "stage_put_gbps",
                       "stage_get_s", "stage_put_s"}
    assert rec["stage_get_gbps"] > 0 and rec["stage_put_gbps"] > 0
    # The seconds fields round to 3 decimals — a warm sub-millisecond CPU
    # transfer legitimately records 0.0.
    assert rec["stage_get_s"] >= 0 and rec["stage_put_s"] >= 0


def test_compact_summary_carries_perf_verdicts():
    """When the on-chip train child holds the perf claims (spec-decode
    exactness, int8 mode speedups, flash crossover), the LAST-line digest
    surfaces them — and stays under the tail budget."""
    import json

    record = {"metric": "m", "value": 1.0, "unit": "GB/s",
              "vs_baseline": 0.5,
              "extra": {"tiers": {"primary": {"combined_gbps": 1.0}}}}
    train = {
        "platform": "tpu", "mfu": 0.45, "tokens_per_s": 1.0,
        "decode": {
            "speculative": {
                "repetitive": {"numerics_ok": True, "speedup": 1.6},
            },
            "int8": {
                "weight_only": {"speedup_vs_fp": 0.8,
                                "token_agreement": 0.97},
                "fused_native": {"speedup_vs_fp": 1.4,
                                 "token_agreement": 0.96},
            },
        },
        "flash_attention": {"measured_crossover_T": 1024},
    }
    s = bench._compact_summary(record, train)
    d = s["summary"]
    assert d["spec_decode"] == {"numerics_ok": True, "speedup": 1.6}
    assert d["int8_fused_native"] == {
        "speedup": 1.4, "token_agreement": 0.96,
    }
    assert d["int8_weight_only"] == {
        "speedup": 0.8, "token_agreement": 0.97,
    }
    assert d["flash_crossover_T"] == 1024
    assert len(json.dumps(s)) < 1000, len(json.dumps(s))


def test_compact_summary_verdicts_need_an_on_chip_train_child():
    """The verdicts ride the train child's own record: a CPU child (or
    none) yields a digest with no device claims at all, whatever the
    record's extra block holds."""
    record = {"metric": "m", "value": 1.0, "unit": "GB/s",
              "vs_baseline": 0.5, "extra": {"tiers": {}}}
    train = {
        "platform": "cpu", "mfu": None, "tokens_per_s": 2.0,
        "decode": {"speculative": {"repetitive": {"numerics_ok": True,
                                                  "speedup": 1.5}}},
        "flash_attention": {"measured_crossover_T": 2048},
    }
    for t in (train, None):
        d = bench._compact_summary(record, t)["summary"]
        assert set(d) == {"host_combined_gbps", "git"}


def test_flash_crossover_fwd_key_and_dual_persist(tmp_path, monkeypatch):
    """ISSUE 4 satellite: the crossover fits independently per path (the
    r5 sweep had fwd winning at T=512 while fwd+bwd lost there), and
    _persist_flash_tuning writes both keys where the dispatcher reads
    them."""
    import importlib
    import json

    recs = {
        "T512": {"numerics_ok": True, "fwd_speedup": 2.73,
                 "fwdbwd_speedup": 0.2},
        "T1024": {"numerics_ok": True, "fwd_speedup": 1.9,
                  "fwdbwd_speedup": 0.9},
        "T2048": {"numerics_ok": True, "fwd_speedup": 1.47,
                  "fwdbwd_speedup": 1.73},
        "T4096": {"numerics_ok": True, "fwd_speedup": 1.5,
                  "fwdbwd_speedup": 2.1},
    }
    assert bench._flash_crossover_from(recs) == 2048
    assert bench._flash_crossover_from(recs, key="fwd_speedup") == 512

    attn = importlib.import_module("tpuflow.ops.attention")
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path))
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ_FWD", raising=False)
    bench._persist_flash_tuning(2048, 512)
    with open(attn.flash_tuning_path()) as f:
        rec = json.load(f)
    assert rec["flash_min_seq"] == 2048
    assert rec["flash_min_seq_fwd"] == 512
    attn._flash_tuning_cache = None
    assert attn._flash_min_seq(needs_bwd=True) == 2048
    assert attn._flash_min_seq(needs_bwd=False) == 512
    # A fwd-only fit with no trusted fwd+bwd crossover persists just its
    # own key; the dispatcher keeps the fwd+bwd default.
    bench._persist_flash_tuning(None, 1024)
    attn._flash_tuning_cache = None
    assert attn._flash_min_seq(needs_bwd=False) == 1024
    assert attn._flash_min_seq(needs_bwd=True) == attn._DEFAULT_FLASH_MIN_SEQ
    attn._flash_tuning_cache = None
