"""The Xing4.0 family's configuration check and arithmetic, and the five
readers that came with it, on plain data."""

import copy
import json
import os

import pytest

from benchmark.harness import manifest

CELL = "serve-xing4-reason"


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_the_manifest_and_the_configuration_file_are_accepted(cell):
    assert manifest.validate(manifest.load_manifest()) == []
    assert cell["family"].check_config(cell["config"]) == []
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "serve_open_loop"


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("kv_lora_rank", 256), ("n_routed_experts", 8),
    ("n_experts_per_tok", 2), ("moe_intermediate_size", 512), ("vocab_size", 32768),
    ("rope_factor", 1), ("n_layer", 5),
])
def test_a_changed_width_or_an_unlisted_cut_is_rejected(cell, key, value):
    cfg = copy.deepcopy(cell["config"])
    cfg["model"][key] = value
    problems = cell["family"].check_config(cfg)
    assert problems and any(key in p or "parameters" in p for p in problems)


def test_a_cut_not_listed_under_reduced_is_rejected(cell):
    cfg = copy.deepcopy(cell["config"])
    cfg["reduced"].remove("num_hidden_layers")
    assert any("not in reduced" in p for p in cell["family"].check_config(cfg))


def test_the_arithmetic_of_the_shapes(cell):
    fam, m = cell["family"], cell["config"]["model"]
    assert fam.n_params(m) == cell["config"]["parameters"] == 5_537_658_874
    # 4 routed + 1 shared expert a layer, not 64
    active = fam.forward_flops_per_token(m) / 2
    assert 1.0e9 < active < 1.2e9 < fam.n_params(m) - m["vocab_size"] * m["hidden_size"]
    assert fam.routed_experts_held(m) == 6 * 64
    assert fam.moe_step_bytes(m, 1) == 3 * 3584 * 1024 * 2
    assert 0 < fam.train_flops_per_token(m) <= 6.0 * fam.n_params(m)


@pytest.mark.parametrize("touched_a_layer", [4, 15, 41, 64])
@pytest.mark.parametrize("live_tokens", [0, 1300, 32 * 4096])
def test_the_least_a_step_reads_never_exceeds_what_it_read(cell, touched_a_layer, live_tokens):
    """`decode_step_bytes` is told nothing of the rows: it counts the four
    experts one row touches, so it is at most the rest + `moe_step_bytes`
    of any number of experts a step can have touched."""
    fam, m = cell["family"], cell["config"]["model"]
    rest = fam.decode_step_bytes(m, live_tokens) - fam.moe_step_bytes(m, 6 * 4)
    assert rest > 0
    read = rest + fam.moe_step_bytes(m, 6 * touched_a_layer)
    assert fam.decode_step_bytes(m, live_tokens) <= read
    assert fam.decode_step_bytes(m, 0) == pytest.approx(2.2086e9, rel=1e-3)


# ------------------------------------------------------------ the readers
def _events(blocks):
    """Three counted requests, and `serve.decode` spans (mono, touched)."""
    ev = []
    for r, t in enumerate((100.0, 101.0, 102.0)):
        ev.append({"kind": "span", "name": "serve.admit", "request": r, "mono": t,
                   "span": 10 + r, "dur_s": 0.01})
        ev.append({"kind": "event", "name": "serve.first_token", "request": r, "mono": t + 0.1})
        ev.append({"kind": "event", "name": "serve.complete", "request": r, "mono": t + 5.0})
    for i, (mono, touched) in enumerate(blocks):
        e = {"kind": "span", "name": "serve.decode", "mono": mono, "span": 100 + i,
             "dur_s": 0.1, "rows": 8, "pages": 128}
        if touched is not None:
            e["experts_touched"] = touched
        ev.append(e)
    return ev


def _run(cell, blocks, trace=True):
    return {
        "cell": cell, "attempted": 3, "peaks": {"hbm_bytes_per_s": 819e9},
        "device": {"count": 1},
        "host": {"window_s": 50.0, "decode_block": 8},
        "traced": {"program_events": _events(blocks), "window_s": 10.0,
                   "trace": {"busy_s": 1.0} if trace else None},
    }


def test_experts_touched_share_on_plain_data(cell):
    read = manifest.load_reader("experts_touched_share.serve")
    # two blocks in the window (8 steps x 6 layers x 64 experts each), one before it
    run = _run(cell, [(90.0, 9999), (110.0, 1536), (120.0, 768)])
    assert read(run) == pytest.approx(100.0 * (1536 + 768) / (2 * 8 * 384))
    assert read(_run(cell, [(110.0, None)])) is None  # a program that counts no experts
    assert read({**run, "traced": {"program_events": []}}) is None


def test_moe_experts_roofline_on_plain_data(cell, monkeypatch):
    from benchmark.harness import scopes

    read = manifest.load_reader("moe_experts_roofline.serve")
    red = {"busy_s": 1.0, "by_tokens": {
        frozenset({"serve.decode", "moe_experts"}): 0.25,
        frozenset({"serve.prefill", "moe_experts"}): 0.5,
        frozenset({"serve.decode", "mhc"}): 0.25,
    }}
    monkeypatch.setattr(scopes, "device", lambda run: red)
    # the traced part is the window's last 10 s: [140, 150)
    run = _run(cell, [(120.0, 5000), (141.0, 1000), (149.0, 500)])
    bytes_read = 1500 * 3 * 3584 * 1024 * 2
    assert read(run) == pytest.approx(100.0 * bytes_read / 819e9 / 0.25)
    monkeypatch.setattr(scopes, "device", lambda run: None)
    assert read(run) is None
    monkeypatch.setattr(scopes, "device", lambda run: {
        "busy_s": 1.0, "by_tokens": {frozenset({"serve.decode", "attn_core"}): 1.0}})
    assert read(run) is None  # a program without the scope


@pytest.mark.parametrize("metric,want", [
    ("moe_share.serve", 40.0), ("attn_latent_share.serve", 15.0), ("mhc_share.serve", 25.0),
])
def test_the_scope_shares_on_plain_data(cell, monkeypatch, metric, want):
    from benchmark.harness import scopes

    red = {"busy_s": 2.0, "by_tokens": {
        frozenset({"serve.decode", "moe_experts"}): 0.5,
        frozenset({"serve.decode", "router"}): 0.1,
        frozenset({"serve.decode", "moe_shared"}): 0.2,
        frozenset({"serve.prefill", "moe_experts"}): 0.3,
        frozenset({"serve.decode", "kv_read"}): 0.1,
        frozenset({"serve.decode", "attn_core"}): 0.2,
        frozenset({"serve.decode", "mhc"}): 0.5,
        frozenset({"serve.prefill", "mhc"}): 0.1,
    }}
    monkeypatch.setattr(scopes, "device", lambda run: red)
    read = manifest.load_reader(metric)
    assert read(_run(cell, [])) == pytest.approx(want)
    monkeypatch.setattr(scopes, "device", lambda run: {
        "busy_s": 2.0, "by_tokens": {frozenset({"serve.decode", "c_attn"}): 2.0}})
    assert read(_run(cell, [])) is None  # the parent's program: nothing to read
    monkeypatch.setattr(scopes, "device", lambda run: None)
    assert read(_run(cell, [], trace=False)) is None


def test_the_cell_lists_the_accepted_readers_that_apply_and_its_own_five(cell):
    names = {m["name"] for m in cell["per_layer"]}
    own = {"moe_share.serve", "moe_experts_roofline.serve", "attn_latent_share.serve",
           "mhc_share.serve", "experts_touched_share.serve"}
    assert own <= names and len(names) == 16
    assert {"mfu.serve", "decode_bw_share.serve", "decode_carry_share.serve"} <= names
    man = manifest.load_manifest()
    for m in man["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p90_ms"
    with open(os.path.join(manifest.BENCH_DIR, "limits", CELL + ".json")) as f:
        assert set(json.load(f)["limits"]) == {
            "widest_logit_gap", "requests_failed", "compiled_in_window"}


# ------------------------------------------- tokens that rounding decides
def _tokens(fam, robust_gaps, fragile_gaps):
    """Plain per-token arrays: gaps of robust tokens, then of fragile ones."""
    import numpy as np

    tau = fam.FRAGILE_MARGIN
    gap = np.array(list(robust_gaps) + list(fragile_gaps), float)
    margin = np.array([tau] * len(robust_gaps) + [tau * 0.99] * len(fragile_gaps))
    return gap, margin


@pytest.mark.parametrize("robust, fragile, want", [
    # the robust tokens give their widest gap; a fragile token's flip is not it
    ([0.0, 0.01, 0.003] * 4, [0.9] + [0.0] * 39, 0.01),
    # one fragile token in forty flipped (bfloat16 reads 1 to 2 in 100): under the quantile
    ([0.002] * 10, [0.5] + [0.0] * 39, 0.002),
    # one in six flipped, as float8 does: the quantile is a flipped token's gap
    ([0.002] * 10, [0.3, 0.4, 0.5, 0.6, 0.7, 0.35, 0.45] + [0.0] * 33, 0.6),
    # a robust token that lost its place is the number whatever the others do
    ([0.0, 0.7], [0.0] * 40, 0.7),
    # under a fifth of the tokens robust: every token is held to the widest gap
    ([0.001] * 5, [0.9] + [0.0] * 39, 0.9),
    # no fragile token at all
    ([0.0, 0.02], [], 0.02),
], ids=["robust-widest", "rare-flips-pass", "float8-flips-fail", "robust-fault", "too-few-robust",
        "none-fragile"])
def test_judge_tokens_holds_robust_tokens_to_the_widest_gap_and_fragile_ones_to_a_quantile(
        cell, robust, fragile, want):
    fam = cell["family"]
    assert 0.003 <= fam.FRAGILE_MARGIN <= 0.01  # the readings: the cell's limits file
    assert fam.judge_tokens(*_tokens(fam, robust, fragile)) == pytest.approx(want)


def test_no_served_token_gives_no_reading_and_so_not_correct(cell):
    import numpy as np
    from benchmark.harness import check

    assert cell["family"].judge_tokens(np.zeros(0), np.zeros(0)) is None
    ok, _ = check.judge({"widest_logit_gap": None, "requests_failed": 0, "compiled_in_window": 0},
                        cell["limits"])
    assert not ok


def test_serve_gaps_judges_program_and_control_over_the_same_tokens(cell, monkeypatch):
    import numpy as np

    fam = cell["family"]
    tau = fam.FRAGILE_MARGIN
    tokens = [
        {"gap": np.array([0.0, 0.9, 0.01]), "low_gap": np.array([0.2, 0.9, 0.6]),
         "margin": np.array([0.02, tau * 0.4, tau]), "layer": np.array([1, 2, 3])},
        {"gap": np.array([0.0]), "low_gap": np.array([1.2]), "margin": np.array([tau * 0.99]),
         "layer": np.array([4])},
    ]
    monkeypatch.setattr(fam._module, "token_gaps", lambda *a, **k: tokens)
    got = fam.serve_gaps(cell["config"]["model"], 1, [], quant="fp8")
    # two of four robust; of the two fragile the quantile is the larger gap
    assert got == {"widest_gap": 0.9, "widest_gap_low": 1.2, "tokens": 4}


def test_router_margin_is_the_lead_of_the_last_chosen_over_the_first_left_out(cell):
    import jax.numpy as jnp

    fam = cell["family"]
    m = {"n_experts_per_tok": 2}
    # one token, four experts: scores through a router of one input
    w = jnp.array([[2.0, 1.0, 0.5, -1.0]])
    lp = {"router": w, "router_bias": jnp.array([0.0, 0.0, 0.1, 0.0])}
    s = 1 / (1 + jnp.exp(-w[0])) + lp["router_bias"]
    got = fam.router_margin(jnp.ones((1, 1)), lp, m)
    assert float(got[0]) == pytest.approx(float(s[1] - s[2]), abs=1e-6)
