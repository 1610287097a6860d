"""The SDAR family's configuration check and arithmetic, the two readers
that came with it on plain data, the replay's plan, and rehearsals of the
serving loop on the CPU at the family's test width: `correct` with the
program as it is, not with the commit pass left out of it, and not with a
causal mask in the block's place."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmark.harness import manifest, runner
from benchmark.tests import cells

CELL = "serve-sdar-blockgen"


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


def test_the_manifest_and_the_configuration_file_are_accepted(cell):
    assert manifest.validate(manifest.load_manifest()) == []
    assert cell["family"].name == "sdar" and cell["family"].check_config(cell["config"]) == []
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "serve_open_loop"
    narrow = {**cell["config"], "model": cell["family"].test_config()["model"]}
    assert cell["family"].check_config(narrow)  # the test width is not the published one


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 1024), ("head_dim", 64), ("n_kv_head", 8), ("n_experts", 64),
    ("n_experts_per_tok", 4), ("moe_intermediate_size", 512), ("vocab_size", 32768),
    ("rope_theta", 10000), ("n_layer", 5), ("n_ctx", 4096),
])
def test_a_changed_width_or_an_unlisted_cut_is_rejected(cell, key, value):
    cfg = copy.deepcopy(cell["config"])
    cfg["model"][key] = value
    problems = cell["family"].check_config(cfg)
    assert problems and any(key in p or "parameters" in p for p in problems)


@pytest.mark.parametrize("key,value", [("block_length", 8), ("denoise_steps", 4), ("mask_id", 7)])
def test_a_generation_that_is_not_the_models_is_rejected(cell, key, value):
    cfg = copy.deepcopy(cell["config"])
    cfg["serve"]["generation"][key] = value
    assert any(key in p for p in cell["family"].check_config(cfg))
    del cfg["serve"]["generation"]
    assert any("block_diffusion" in p for p in cell["family"].check_config(cfg))


def test_a_cut_not_listed_under_reduced_is_rejected(cell):
    cfg = copy.deepcopy(cell["config"])
    cfg["reduced"].remove("num_hidden_layers")
    assert any("not in reduced" in p for p in cell["family"].check_config(cfg))


def test_the_arithmetic_of_the_shapes(cell):
    fam, m = cell["family"], cell["config"]["model"]
    assert fam.n_params(m) == cell["config"]["parameters"] == 4_984_176_384
    layer = 18_874_368 + 256 + 4_096 + 262_144 + 603_979_776  # the issue's reckoning
    assert fam.n_params(m) == 7 * layer + 2 * 151_936 * 2_048 + 2_048
    # 8 routed experts a layer, not 128
    active = fam.forward_flops_per_token(m) / 2
    assert active == 151_936 * 2_048 + 2_048 + 7 * (layer - 120 * 3 * 2_048 * 768)
    assert fam.routed_experts_held(m) == 7 * 128
    assert fam.moe_step_bytes(m, 1) == 3 * 2048 * 768 * 2
    assert 0 < fam.train_flops_per_token(m) <= 6.0 * fam.n_params(m)
    assert fam.positions(m) == 2048 and fam.vocabulary(m) == 151_936
    # the pool's two leaves hold whole 128-lane rows a token
    assert m["n_kv_head"] * m["head_dim"] == 512
    serve = cell["config"]["serve"]
    assert serve["n_pages"] * 16 * 7 * 2 * 512 * 2 == 1_879_277_568  # the pool, in bytes


@pytest.mark.parametrize("touched_a_layer", [8, 40, 110, 128])
@pytest.mark.parametrize("live_tokens", [0, 900, 32 * 2048])
def test_the_least_a_pass_reads_never_exceeds_what_it_read(cell, touched_a_layer, live_tokens):
    """`decode_bw_share.serve` multiplies this by the passes of the traced
    calls: it must not count more than any pass reads, a commit pass (no
    head) among them, whatever the experts touched."""
    fam, m = cell["family"], cell["config"]["model"]
    least = fam.decode_step_bytes(m, live_tokens)
    head = (151_936 * 2_048 + 2_048) * 2
    resident = 7 * (18_874_368 + 256 + 4_096 + 262_144) * 2
    kv = 7 * live_tokens * 2 * 512 * 2
    mean_pass = resident + head * 2 / 3 + fam.moe_step_bytes(m, 7 * touched_a_layer) + kv
    assert least <= mean_pass
    assert fam.decode_step_bytes(m, 0) == pytest.approx(1.2113e9, rel=1e-3)


# ------------------------------------------------------------ the readers
def _events(calls):
    """Three counted requests, and `serve.decode` spans (mono, attributes)."""
    ev = []
    for r, t in enumerate((100.0, 101.0, 102.0)):
        ev.append({"kind": "span", "name": "serve.admit", "request": r, "mono": t,
                   "span": 10 + r, "dur_s": 0.01})
        ev.append({"kind": "event", "name": "serve.first_token", "request": r, "mono": t + 0.1})
        ev.append({"kind": "event", "name": "serve.complete", "request": r, "mono": t + 5.0})
    for i, (mono, attrs) in enumerate(calls):
        ev.append({"kind": "span", "name": "serve.decode", "mono": mono, "span": 100 + i,
                   "dur_s": 0.1, "rows": 8, "pages": 128, **attrs})
    return ev


def _run(cell, calls, trace=True):
    return {
        "cell": cell, "attempted": 3, "peaks": {"hbm_bytes_per_s": 819e9}, "device": {"count": 1},
        "host": {"window_s": 50.0, "decode_block": 6},
        "traced": {"program_events": _events(calls), "window_s": 2.0,
                   "trace": {"busy_s": 1.0} if trace else None},
    }


def test_tokens_per_pass_on_plain_data(cell):
    read = manifest.load_reader("tokens_per_pass.serve")
    full = {"tokens": 64, "slots": 8, "passes": 6}      # 8 rows x 2 blocks of 4
    short = {"tokens": 21, "slots": 5, "passes": 6}     # rows that ended inside the call
    run = _run(cell, [(90.0, {"tokens": 999, "slots": 1, "passes": 6}), (110.0, full), (120.0, short)])
    assert read(run) == pytest.approx((64 + 21) / (8 * 6 + 5 * 6))
    assert read(_run(cell, [(110.0, full)])) == pytest.approx(4 / 3)
    # the parent's spans carry tokens and slots and no passes: nothing to read
    assert read(_run(cell, [(110.0, {"tokens": 64, "slots": 8})])) is None
    assert read(_run(cell, [])) is None
    assert read({**_run(cell, []), "traced": None}) is None


def test_commit_pass_share_on_plain_data(cell, monkeypatch):
    from benchmark.harness import scopes

    red = {"busy_s": 2.0, "by_tokens": {
        frozenset({"serve.decode", "denoise", "moe_experts"}): 0.9,
        frozenset({"serve.decode", "denoise", "lm_head"}): 0.2,
        frozenset({"serve.decode", "denoise", "unmask"}): 0.1,
        frozenset({"serve.decode", "denoise.commit", "moe_experts"}): 0.45,
        frozenset({"serve.decode", "denoise.commit", "attn_core"}): 0.05,
        frozenset({"serve.prefill", "moe_experts"}): 0.3,
    }}
    monkeypatch.setattr(scopes, "device", lambda run: red)
    read = manifest.load_reader("commit_pass_share.serve")
    assert read(_run(cell, [])) == pytest.approx(25.0)
    assert manifest.load_reader("moe_share.serve")(_run(cell, [])) == pytest.approx(67.5)
    monkeypatch.setattr(scopes, "device", lambda run: {
        "busy_s": 2.0, "by_tokens": {frozenset({"serve.decode", "moe_experts"}): 2.0}})
    assert read(_run(cell, [])) is None  # the parent's program: nothing to read
    monkeypatch.setattr(scopes, "device", lambda run: None)
    assert read(_run(cell, [], trace=False)) is None
    # a scope path as the profile gives it splits into the tokens the reader asks for
    path = "jit(<unknown>)/serve.decode/while/body/closed_call/denoise.commit/Sdar/layers/moe_experts/gmm"
    assert {"serve.decode", "denoise.commit", "moe_experts"} <= scopes.tokens(path)
    assert "denoise" not in scopes.tokens(path)


def test_the_cell_lists_the_accepted_readers_that_apply_and_its_own_two(cell):
    names = [m["name"] for m in cell["per_layer"]]
    own = ["tokens_per_pass.serve", "commit_pass_share.serve"]
    assert names[-2:] == own and len(names) == 16
    assert {"mfu.serve", "decode_bw_share.serve", "decode_carry_share.serve", "moe_share.serve",
            "moe_experts_roofline.serve", "experts_touched_share.serve"} <= set(names)
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_p90_ms", "setup_s"]
    man = manifest.load_manifest()
    assert [m["name"] for m in man["per_layer"][-2:]] == own
    for m in man["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p90_ms"
    assert man["workloads"][-1]["name"] == CELL and man["configs"][-1]["name"] == cell["config_name"]
    with open(os.path.join(manifest.BENCH_DIR, "limits", CELL + ".json")) as f:
        assert set(json.load(f)["limits"]) == {
            "widest_logit_gap", "requests_failed", "compiled_in_window"}
    fam = cell["family"]
    assert {"attn_core", "kv_read", "kv_write", "moe_experts", "router", "lm_head"} <= set(fam.BLOCK_SCOPES)


# ------------------------------------------------------- the replay's plan
@pytest.mark.parametrize("prompt_len,served,steps,want_start,want_pass", [
    (8, 6, 2, 8, [0, 0, 1, 1, 0, 0, -1, -1]),          # the budget ends inside the second block
    (5, 7, 2, 4, [-1, 0, 0, 1, 0, 0, 1, 1]),           # one prompt token opens the first block
    (7, 1, 2, 4, [-1, -1, -1, 0]),                     # three do
    (6, 3, 4, 4, [-1, -1, 0, 1, 0, -1, -1, -1]),       # one position a pass
    (4, 4, 1, 4, [0, 0, 0, 0]),                        # the whole block in one pass
])
def test_replay_plan_names_the_pass_of_every_served_token(prompt_len, served, steps, want_start, want_pass):
    fam = manifest.load_family("sdar")
    m = {"block_length": 4, "denoise_steps": steps}
    plan = fam.replay_plan(prompt_len, served, m)
    assert plan["start"] == want_start and list(plan["pass_of"]) == want_pass
    assert int((plan["pass_of"] >= 0).sum()) == served
    for s in range(steps):  # a pass runs with what the passes before it unmasked, and the prompt's
        earlier = (plan["pass_of"] >= 0) & (plan["pass_of"] < s)
        prompt = want_start + np.arange(len(want_pass)) < prompt_len
        assert list(plan["known"][s]) == list(earlier | prompt)


def test_the_joined_sequence_shows_a_copys_block_the_clean_blocks_before_it_and_itself():
    fam = manifest.load_family("sdar")
    m = {"block_length": 4, "denoise_steps": 2, "mask_id": 99}
    prompt, served = np.arange(1, 7), np.arange(11, 18)  # 6 + 7: blocks 1, 2, 3 generate
    j = fam.joined(prompt, served, m, length=16, copy=12)
    assert j["ids"].shape == (16 + 2 * 12,) and j["visible"].shape == (40, 40)
    # pass 0 of block 1 knows the prompt's two tokens; pass 1 two more
    assert list(j["ids"][16:20]) == [5, 6, 99, 99] and list(j["ids"][28:32]) == [5, 6, 11, 12]
    assert list(j["pos"][16:28]) == list(range(4, 16)) == list(j["pos"][28:40])
    see = j["visible"]
    assert see[16, :4].all() and not see[16, 4:16].any()      # clean block 0 alone
    assert see[16, 16:20].all() and not see[16, 20:].any()    # its own block, both ways
    assert see[21, :8].all() and not see[21, 8:16].any() and see[21, 20:24].all()
    assert not see[:16, 16:].any()                            # nothing clean sees a copy
    assert see[5, :8].all() and not see[5, 8:16].any()        # the clean sequence, block-causal
    # served token i: its row is its position in the copy of the pass that unmasked it
    assert list(j["rows"]) == [16 + 2, 16 + 3, 16 + 4, 16 + 5, 28 + 6, 28 + 7, 16 + 8]


# ------------------------------------------------------------- rehearsals
def rehearse(seed: int = 2**31 + 37) -> dict:
    return runner.run_cell(
        cells.cell("serve_open_loop", "sdar"), seed=seed, seconds=1.5, trace=False,
        t_start=time.monotonic(), reach_chip_s=0.0, rehearse=True,
    )


def test_the_loop_serves_the_family_and_the_replay_finds_it_correct():
    result = rehearse()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    value, limit = result["compared"]["widest_logit_gap"]
    assert value <= limit == 1e-3
    assert result["compared"]["compiled_in_window"][0] == 0


def test_without_the_commit_pass_the_served_tokens_are_not_correct(monkeypatch):
    """The commit pass's write dropped: later blocks read keys and values
    that a denoise pass wrote of a block still half masked."""
    from tpuflow.models.sdar import Sdar

    real = Sdar.apply

    def no_commit(self, variables, *args, head=True, **kw):
        out, mutated = real(self, variables, *args, head=head, **kw)
        if not head:
            mutated = {**mutated, "cache": variables["cache"]}
        return out, mutated

    monkeypatch.setattr(Sdar, "apply", no_commit)
    result = rehearse()
    assert result["correct"] is False and result["failed"] == 0
    value, limit = result["compared"]["widest_logit_gap"]
    assert value > 10 * limit


def test_with_a_causal_mask_in_the_blocks_place_the_served_tokens_are_not_correct(monkeypatch):
    from tpuflow.models import sdar

    monkeypatch.setattr(
        sdar, "block_causal", lambda q_pos, k_pos, _: k_pos[..., None, :] <= q_pos[..., :, None]
    )
    result = rehearse()
    assert result["correct"] is False and result["failed"] == 0
    value, limit = result["compared"]["widest_logit_gap"]
    assert value > 10 * limit
