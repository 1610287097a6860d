"""The family seam: how a family is found, what it is told when it lacks
something, that the harness itself names no family and reads no key of one,
and a family the harness has never heard of (`data/toy_root/`, a root of
its own) driven through the same `train_steady` loop and `TrainRig` as
every other."""

import glob
import json
import os
import re
import time

import pytest

from benchmark.harness import check, manifest, readers, runner, traffic
from benchmark.tests import cells

TOY_ROOT = os.path.join(os.path.dirname(__file__), "data", "toy_root")
TOY_CELL = "toy-train-steady"
SHUT = ("loops", "layer_metrics", "harness")  # the directories the seam keeps shut


# ------------------------------------------------------ finding a family
def test_an_unknown_family_a_bad_name_and_a_missing_function(tmp_path):
    with pytest.raises(manifest.ManifestError, match="no family 'no_such_family'"):
        manifest.load_family("no_such_family")
    for bad in ("../gpt2", "a b", "", None, 7):
        with pytest.raises(manifest.ManifestError, match="bad family name"):
            manifest.load_family(bad)
    os.makedirs(tmp_path / "benchmark" / "families")
    (tmp_path / "benchmark" / "families" / "half.py").write_text("def positions(m):\n    return 8\n")
    half = manifest.load_family("half", str(tmp_path))
    assert half.positions({}) == 8
    with pytest.raises(manifest.ManifestError, match=r"family 'half' \(.*half\.py\) has no 'make_params'"):
        half.make_params
    with pytest.raises(AttributeError):  # what copy and pickle look up is no family function
        half.__deepcopy__


def test_a_cell_carries_its_family_and_loads_it_only_when_asked():
    cell = manifest.load_cell(manifest.load_manifest()["workloads"][0]["name"])
    fam = cell["family"]
    assert fam.name == cell["config"]["family"] and fam._module is None
    assert fam.positions(cell["config"]["model"]) > 0 and fam._module is not None


@pytest.mark.parametrize("family", cells.families())
def test_a_family_on_disk_exposes_what_the_loops_and_readers_ask(family):
    fam = manifest.load_family(family)
    config = fam.test_config()
    m = config["model"]
    for name in ("module", "make_params", "leaf_norms", "delta_norms", "train_reference",
                 "check_config", "n_params", "train_flops_per_token", "attention_flops"):
        assert callable(getattr(fam, name)), name
    if "serve" in config:
        for name in ("serve_gaps", "forward_flops_per_token", "decode_step_bytes"):
            assert callable(getattr(fam, name)), name
    assert fam.n_params(m) > 0 and 0 < fam.train_flops_per_token(m) <= 6.0 * fam.n_params(m)
    assert fam.positions(m) >= 64 and fam.vocabulary(m) >= 64
    assert fam.BLOCK_SCOPES and fam.MODULE_SCOPES
    assert set(config["limits"]) <= {"train", "serve"}


# ------------------------------------------------ the guard on the seam
def _sources():
    for directory in SHUT:
        for path in sorted(glob.glob(os.path.join(manifest.BENCH_DIR, directory, "*.py"))):
            with open(path) as f:
                yield os.path.relpath(path, manifest.BENCH_DIR), f.read()


def test_the_harness_the_loops_and_the_readers_name_no_family_and_read_no_key_of_one():
    names = "|".join(re.escape(n) for n in cells.families())
    forbidden = {
        "imports a model of the program": re.compile(r"tpuflow\.models"),
        "reads a key of one family's configuration": re.compile(
            r"\b(n_embd|n_head|n_layer|n_ctx|hidden_size|num_hidden_layers|num_attention_heads)\b"),
        "names a family": re.compile(rf"(?i)(?<![a-z0-9])({names})(?![a-z])"),
    }
    found = [
        f"{path}:{no}: {what}: {line.strip()}"
        for path, text in _sources()
        for no, line in enumerate(text.splitlines(), 1)
        for what, pattern in forbidden.items() if pattern.search(line)
    ]
    assert found == []


def test_what_moved_behind_the_seam_is_gone_and_only_families_and_tools_import_a_model():
    assert sum(1 for _ in _sources()) > 30  # the guard above looked at something
    for gone in ("flops.py", "weights.py"):
        assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "harness", gone))
    only = [
        os.path.relpath(p, manifest.BENCH_DIR)
        for p in glob.glob(os.path.join(manifest.BENCH_DIR, "**", "*.py"), recursive=True)
        if "tpuflow.models" in open(p).read() and os.sep + "tests" + os.sep not in p
    ]
    assert only and all(p.split(os.sep)[0] in ("families", "tools") for p in only)


def test_nothing_outside_the_toy_root_names_the_toy():
    for path in glob.glob(os.path.join(manifest.BENCH_DIR, "**", "*"), recursive=True):
        if os.path.isfile(path) and not path.startswith(TOY_ROOT) and path.endswith((".py", ".json", ".sh")):
            if os.path.abspath(path) == os.path.abspath(__file__):
                continue
            with open(path) as f:
                assert "toy" not in f.read().lower().replace("toy size", ""), path


# --------------------------------------- a family of files alone: the toy
def toy_cell() -> dict:
    return manifest.load_cell(TOY_CELL, TOY_ROOT)


def toy_run(cell=None, seed: int = 2**31 + 29) -> dict:
    return runner.run_cell(
        cell or toy_cell(), seed=seed, seconds=1.0, trace=False,
        t_start=time.monotonic(), reach_chip_s=0.0, rehearse=True,
    )


def test_the_toy_manifest_is_valid_and_a_wrong_parameter_count_is_not(tmp_path):
    man = manifest.load_manifest(TOY_ROOT)
    assert manifest.validate(man, TOY_ROOT) == []
    assert os.path.isdir(os.path.join(TOY_ROOT, "benchmark", "families"))
    entry = man["configs"][0]
    with open(os.path.join(TOY_ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["model"]["num_hidden_layers"] < cfg["num_hidden_layers"]  # a depth cut passes
    assert set(entry["reduced"]) == {"num_hidden_layers", "vocab_size"}
    import shutil

    shutil.copytree(TOY_ROOT, tmp_path / "root")
    cfg["parameters"] += 1
    with open(tmp_path / "root" / entry["file"], "w") as f:
        json.dump(cfg, f)
    problems = manifest.validate(man, str(tmp_path / "root"))
    assert len(problems) == 1 and "parameters" in problems[0] and entry["name"] in problems[0]


def test_the_toy_rehearses_through_the_same_loop_and_rig_as_every_family():
    from benchmark.loops import _train, train_steady

    cell = toy_cell()
    assert cell["traffic"]["kind"] == "train_steady" and cell["family"].name == "toy"
    built = []
    real = _train.TrainRig.__init__

    def watched(self, cell, seed):
        built.append(cell["family"].name)
        real(self, cell, seed)

    try:
        _train.TrainRig.__init__ = watched
        result = toy_run(cell)
    finally:
        _train.TrainRig.__init__ = real
    assert built == ["toy"] and train_steady.TrainRig is _train.TrainRig
    assert result["correct"] is True and result["attempted"] > 0 and result["rehearsal"] is True
    assert set(result["compared"]) == {"loss1_gap", "loss2_gap", "grad_gap", "dparam_gap"}
    for value, limit in result["compared"].values():
        assert value <= limit
    # the vocabulary the traffic drew from is the slice, not the published one
    m = cell["config"]["model"]
    assert cell["family"].vocabulary(m) == 512 < cell["config"]["vocab_size"]


def test_the_toy_with_its_step_left_unchanged_is_not_correct(monkeypatch):
    from benchmark.tests.test_rehearsal import _break_step

    _break_step(monkeypatch, "unchanged")
    result = toy_run()
    assert result["correct"] is False
    value, limit = result["compared"]["dparam_gap"]
    assert value > 0.99 > limit


@pytest.mark.parametrize("control", ["fp8", "bf16"])
def test_the_toy_with_a_control_in_the_references_place_is_not_correct(control):
    cell = toy_cell()
    fam = cell["family"]
    plain = fam.train_reference
    # the reference computed in a lower precision, put where the loop looks for it
    fam.train_reference = lambda *a, **kw: plain(*a, quant=control, **kw)
    result = toy_run(cell)
    assert result["correct"] is False
    assert result["compared"]["grad_gap"][0] > result["compared"]["grad_gap"][1]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_toys_controls_and_half_a_batch_fail_its_limits(seed):
    cell = toy_cell()
    fam, m, opt = cell["family"], cell["config"]["model"], cell["config"]["optimizer"]
    corpus = traffic.lm_corpus(seed, 64, 64, fam.vocabulary(m))
    batches = [(corpus[i * 4:(i + 1) * 4, :-1], corpus[i * 4:(i + 1) * 4, 1:]) for i in range(3)]
    ref = fam.train_reference(m, opt, seed, batches, rows_per_block=2)
    again = fam.train_reference(m, opt, seed, batches, rows_per_block=4)
    assert check.judge(check.compare_train(again, ref), cell["limits"])[0]
    for kw in ({"quant": "bf16"}, {"quant": "fp8"}, {"fault": "half_batch"}):
        other = fam.train_reference(m, opt, seed, batches, rows_per_block=2, **kw)
        ok, compared = check.judge(check.compare_train(other, ref), cell["limits"])
        assert not ok, (kw, compared)


def test_a_shared_reader_asks_the_cells_family_for_its_counts():
    cell = toy_cell()
    fam, m = cell["family"], cell["config"]["model"]
    run = {"cell": cell, "device": {"count": 1}, "peaks": {"bf16_flops_per_s": 197e12},
           "host": {"tokens_per_step": 256, "traced": {"s": 2.0, "steps": 4}}}
    per_token = 6.0 * (fam.n_params(m) - 512 * 64)  # the embedding is read, not multiplied
    assert readers.train_mfu(run, 4) == pytest.approx(100.0 * per_token * (4 * 256 / 2.0) / 197e12)
    with pytest.raises(manifest.ManifestError, match="family 'toy' .* has no 'serve_gaps'"):
        fam.serve_gaps


# ------------------------------------- the tools go through the family too
def test_the_tools_that_read_a_training_cells_limits_run_on_any_family():
    from benchmark.tools import calibrate, first_steps

    row = calibrate.train_readings(toy_cell(), 3)
    assert row["control_fp8"]["grad_gap"] > 1e-3 and row["fault_half_batch"]["grad_gap"] > 0.3
    row = first_steps.readings(toy_cell(), 3, ["bf16", "reorder"])
    assert row["program"]["grad_gap"] < 1e-5 < row["bf16"]["grad_gap"]
    assert row["reorder"]["grad_gap"] < 1e-5 and len(row["reference_losses"]) == 3


@pytest.mark.parametrize("family", [f for f, k in cells.pairs() if k == "serve_open_loop"])
def test_the_tools_that_read_a_serving_cell_run_through_its_family(family):
    from benchmark.tools import calibrate, sweep

    cell = cells.cell("serve_open_loop", family)
    row = calibrate.serve_readings(cell, 5, 1.5)
    assert row["failed"] == 0 and row["tokens"] > 0
    assert row["control_fp8_widest_gap"] >= row["program_widest_gap"]
    assert "serve_gaps" not in vars(cell["family"])  # the tool put the family's own back
    row = sweep.one(cell, 4.0, 1.5, 5)
    assert row["rate_per_s"] == 4.0 and row["correct"] and row["attempted"] > 0
