"""chip_smoke.py, the repository's proof that it starts on the chip: it
fails at once off the chip and outside a checkout, any failed stage makes
the exit code non-zero and suppresses the result line, and only a run on
``platform=tpu`` may print ``"ok": true``. The slow test runs the CPU
rehearsal (the same train → resume → eval → serve → kernels path at the
``test`` preset) end to end."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except ValueError:
        return None


def test_fails_fast_without_a_tpu(tmp_path):
    """Held to the CPU, the default mode exits non-zero with a message
    naming the platform it found, and prints no result."""
    p = subprocess.run(
        [sys.executable, SCRIPT, "--log-dir", str(tmp_path)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert "platform='cpu', not a TPU" in p.stdout
    assert _last_json(p.stdout) is None


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    p = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not a tpuflow checkout" in p.stderr


@pytest.mark.parametrize("failing", [None, *chip_smoke.STAGES])
def test_any_failed_stage_fails_the_run(failing, tmp_path, monkeypatch, capsys):
    """Control flow with the stages stubbed out: each stage either passes
    or ends the run non-zero with no result line; with all passing, the
    result names the device and says ok only for a TPU."""
    def stub(name):
        def run(self):
            if name == failing:
                raise chip_smoke.StageFailed(f"{name}: injected")
            if name == "device":
                self.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        return run

    for name in chip_smoke.STAGES:
        monkeypatch.setattr(chip_smoke.Smoke, f"stage_{name}", stub(name))
    rc = chip_smoke.main(["--log-dir", str(tmp_path)])
    out = capsys.readouterr().out
    if failing is None:
        assert rc == 0
        assert _last_json(out) == {
            "ok": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        }
    else:
        assert rc != 0
        assert f"FAILED {failing}: injected" in out
        assert _last_json(out) is None


@pytest.mark.slow
def test_cpu_rehearsal_end_to_end(tmp_path):
    p = subprocess.run(
        [sys.executable, SCRIPT, "--rehearse-cpu", "--log-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stdout[-4000:]
    lines = p.stdout.strip().splitlines()
    # A rehearsal is never a pass of the chip check.
    assert json.loads(lines[-1]) == {
        "ok": False, "rehearsal": "passed",
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    # Every other line names the device it ran on.
    assert all(ln.startswith("[chip_smoke platform=") for ln in lines[:-1])
    for stage in chip_smoke.STAGES:
        assert any(f"stage {stage}: passed" in ln for ln in lines)
    with open(tmp_path / "report.json") as f:
        report = json.load(f)
    assert report["resume"]["checkpoint_steps"] == [9, 12]
    assert report["serve"]["answered"] == report["serve"]["requests"] == 6
    assert report["kernels"]["interpret"] is True


def test_kernel_stage_checks_the_shape_auto_trains_with():
    """The kernels stage compiles the attention kernels at the row the
    train stage runs (``--attn-impl auto``, 1,024 positions, heads of
    64), where ``auto`` takes them on a TPU since ISSUE 31; the stage
    fails a chip run in which ``auto`` chose otherwise."""
    from tpuflow.ops import flash_attention as fa
    from tpuflow.ops.attention import resolve_attention_impl

    f = chip_smoke.CHIP["flash"]
    assert f["T"] == chip_smoke.CHIP["seq_len"] == 1024 and f["D"] == 64
    assert resolve_attention_impl(
        "auto", (f["B"], f["T"], f["H"], f["D"]), f["T"], backend="tpu"
    ) == "flash"
    assert fa.flash_tiles(f["T"], f["T"], f["H"], f["D"])
    assert fa._block_sizes(f["T"], f["T"]) == (1024, 1024, 256, 256)
    assert fa._head_group(f["H"], f["D"]) == 2


def test_kernel_stage_rehearsal(tmp_path):
    """The kernels child alone, as the rehearsal runs it: the forward,
    and the fused backward against XLA in the interpreter, and what
    ``auto`` picks off the chip."""
    ctx = {"rehearse": True, "size": chip_smoke.REHEARSAL}
    p = subprocess.run(
        [sys.executable, SCRIPT, "--stage", "kernels", "--ctx", json.dumps(ctx)],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = [ln for ln in p.stdout.splitlines()
            if ln.startswith(chip_smoke.RESULT_TAG)][-1]
    out = json.loads(line[len(chip_smoke.RESULT_TAG):])
    flash = out["flash"]
    assert flash["blocks"] == [64, 64, 64, 64]
    assert flash["heads_per_program"] == 2
    assert max(flash["fwd"], flash["bwd_fused"]) <= 1e-4
    assert out["auto"]["attention_train_T64"] == "xla"
