"""The manifest's names, units and files; the peaks table; the readers."""

import json
import os

import pytest

from benchmark.harness import manifest, peaks


def test_manifest_is_valid_and_files_exist():
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", "", "x" * 65, "µs"])
def test_bad_names_are_refused(bad):
    assert not manifest.NAME_RE.match(bad)


@pytest.mark.parametrize("unit,ok", [("tokens/s", True), ("%", True), ("GB/s", True),
                                     ("tokens per second", False), ("µs", False),
                                     ("x" * 17, False)])
def test_units(unit, ok):
    assert bool(manifest.UNIT_RE.match(unit)) is ok


def test_validate_reports_a_bad_unit_and_a_missing_reader():
    man = manifest.load_manifest()
    man["end_to_end"][0]["unit"] = "tokens per second"
    man["per_layer"][0]["name"] = "no_such_reader"
    problems = "\n".join(manifest.validate(man))
    assert "bad unit" in problems and "no reader file" in problems


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    man = manifest.load_manifest()
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert m["moves"] in e2e  # a layer metric's cells report what it moves
            assert callable(manifest.load_reader(m["name"]))
        assert cell["limits"]


def test_every_per_layer_cell_reports_the_metric_it_moves():
    man = manifest.load_manifest()
    for m in man["per_layer"]:
        for w in m["workloads"]:
            e2e = {e["name"] for e in manifest.load_cell(w)["end_to_end"]}
            assert m["moves"] in e2e, (m["name"], w)


def test_config_files_hold_what_is_run_and_name_every_changed_key():
    man = manifest.load_manifest()
    for c in man["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # widths, depth and the parameter count: the family's own check
        assert manifest.load_family(cfg["family"]).check_config(cfg) == []


@pytest.mark.parametrize("config", [c["name"] for c in manifest.load_manifest()["configs"]])
def test_a_configuration_made_wrong_is_reported_by_its_family(config, tmp_path):
    man = manifest.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == config)
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    fam = manifest.load_family(cfg["family"])
    assert fam.n_params(cfg["model"]) == cfg["parameters"]
    assert fam.check_config({**cfg, "parameters": cfg["parameters"] + 1})
    width = next(k for k, v in cfg["model"].items() if isinstance(v, int) and cfg.get(k) == v)
    assert fam.check_config({**cfg, "model": {**cfg["model"], width: cfg[width] * 2}})
    # and `validate` says which configuration: a file that names no family
    os.makedirs(tmp_path / os.path.dirname(entry["file"]))
    with open(tmp_path / entry["file"], "w") as f:
        json.dump({k: v for k, v in cfg.items() if k != "family"}, f)
    problems = manifest._config_problems(entry, str(tmp_path))
    assert problems == ["bad family name None"]


def test_missing_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
        peaks.peaks_for("TPU v9 imaginary")


def test_flops_and_bytes_from_shapes():
    flops = manifest.load_family("gpt2")
    m = {"vocab_size": 50257, "n_ctx": 1024, "n_embd": 1024, "n_layer": 24, "n_head": 16}
    n = flops.n_params(m)
    assert n == 354_823_168
    assert flops.train_flops_per_token(m) == 6.0 * n
    no_ctx = flops.decode_step_bytes(m, 0)
    assert no_ctx == (n - 1024 * 1024) * 4
    assert flops.decode_step_bytes(m, 1000) - no_ctx == 2 * 24 * 1000 * 1024 * 4


def test_mfu_serve_leaves_out_prompt_tokens_that_the_prefix_cache_served():
    flops = manifest.load_family("gpt2")
    m = {"vocab_size": 50257, "n_ctx": 1024, "n_embd": 1024, "n_layer": 24, "n_head": 16}
    run = {
        "cell": {"config": {"model": m}, "family": flops}, "device": {"count": 1},
        "peaks": {"bf16_flops_per_s": 197e12},
        "host": {"prompt_tokens": 1000, "output_tokens": 200, "window_s": 2.0, "page_size": 16,
                 "counters": {"open": {"prefix_hits": 10}, "close": {"prefix_hits": 40}}},
    }
    read = manifest.load_reader("mfu.serve")
    computed = 1000 - 30 * 16 + 200
    assert read(run) == pytest.approx(100.0 * 2.0 * flops.n_params(m) * computed / 2.0 / 197e12)
    run["host"]["counters"] = {}
    assert read(run) == pytest.approx(100.0 * 2.0 * flops.n_params(m) * 1200 / 2.0 / 197e12)
