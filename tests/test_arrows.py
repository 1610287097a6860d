"""Which package of ``tpuflow`` may import which (README.md, "Architecture").

AST only: no module of the package is imported, so this costs no jax.
Imports inside functions count: a lazy import is still an arrow.

``BELOW`` is the packages bottom to top; an arrow points down when it goes
to a package earlier in it. ``ALLOWED`` is each package's down arrows as
they are today, written out so that a new one is a conscious edit here.
``UP`` is the arrows that point up today, by name, each a debt under
ROADMAP.md D12: a new one fails, and so does one that has been repaired
and is still listed.
"""

from __future__ import annotations

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tpuflow")

BELOW = (
    "utils", "_native", "obs",
    "dist", "ops", "parallel", "models", "data", "ckpt",
    "train", "infer",
    "flow", "testing", "lint",
)

ALLOWED = {
    "utils": set(),
    "_native": {"utils"},
    "obs": {"utils"},
    "dist": {"utils", "obs"},
    "ops": {"utils", "obs"},
    "parallel": {"obs", "dist", "ops"},
    "models": {"ops", "parallel"},
    "data": {"utils", "_native", "obs", "dist"},
    "ckpt": {"utils", "_native", "obs"},
    "train": {"utils", "_native", "obs", "dist", "parallel", "models", "data", "ckpt"},
    "infer": {"utils", "obs", "dist", "ops", "ckpt"},
    "flow": {"utils", "obs", "dist", "ckpt", "train"},
    "testing": {"utils", "obs", "infer"},
    "lint": {"utils", "obs"},
}

# ROADMAP.md D12 "Arrows that point up". Repair one, then take it out here.
UP = {
    ("utils", "testing"): "utils/heartbeat.py reads testing.faults (fault injection in production code)",
    ("ckpt", "testing"): "ckpt/raw.py and ckpt/manager.py read testing.faults",
    ("train", "testing"): "train/gpt.py and train/trainer.py read testing.faults",
    ("flow", "testing"): "flow/runner.py and flow/gang_exec.py read testing.faults",
    ("ckpt", "infer"): "ckpt/manager.py imports infer/kv_store",
    ("train", "infer"): "train/gpt.py samples through infer.generate",
    ("ops", "parallel"): "ops/attention.py asks parallel.sharding for the mesh and dispatches to ring and ulysses",
    ("parallel", "models"): "parallel/pipeline.py builds models.gpt2 blocks",
}


def _arrows(package: str) -> dict[str, str]:
    """Packages of tpuflow that ``package``'s files import -> one place."""
    found: dict[str, str] = {}
    for d, _, names in os.walk(os.path.join(PKG, package)):
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(d, name)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                targets: list[str] = []
                if isinstance(node, ast.ImportFrom) and not node.level and node.module:
                    parts = node.module.split(".")
                    if parts[0] == "tpuflow":
                        targets = [a.name for a in node.names] if len(parts) == 1 else [parts[1]]
                elif isinstance(node, ast.Import):
                    targets = [
                        a.name.split(".")[1] for a in node.names if a.name.startswith("tpuflow.")
                    ]
                for t in targets:
                    if t != package and t in BELOW:
                        found.setdefault(t, f"{os.path.relpath(path, PKG)}:{node.lineno}")
    return found


def test_every_package_is_placed():
    on_disk = {
        n for n in os.listdir(PKG)
        if os.path.isfile(os.path.join(PKG, n, "__init__.py"))
    }
    assert on_disk == set(BELOW) == set(ALLOWED)
    for package, allowed in ALLOWED.items():
        up = {t for t in allowed if BELOW.index(t) > BELOW.index(package)}
        assert not up, f"ALLOWED[{package!r}] points up at {up}"
    for (package, target), _why in UP.items():
        assert BELOW.index(target) > BELOW.index(package), (package, target)


@pytest.mark.parametrize("package", BELOW)
def test_arrows_point_down(package):
    found = _arrows(package)
    excepted = {t for (p, t) in UP if p == package}
    new = {t: at for t, at in found.items() if t not in ALLOWED[package] | excepted}
    assert not new, (
        f"tpuflow/{package} imports {new}: an arrow that is not in ALLOWED. Down arrows are "
        "added there; one that points up is a design change (ROADMAP.md D12)"
    )
    repaired = excepted - set(found)
    assert not repaired, f"no longer imported by tpuflow/{package}: take {repaired} out of UP"
    gone = ALLOWED[package] - set(found)
    assert not gone, f"tpuflow/{package} no longer imports {gone}: take it out of ALLOWED"
