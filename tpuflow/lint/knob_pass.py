"""Pass 1 — the TPUFLOW_* knob-registry contract.

Rules:

- ``knob-raw-env``      — a raw ``os.environ`` read (``.get``, subscript
  load, ``in`` membership, ``os.getenv``) of a ``TPUFLOW_*`` name
  anywhere outside ``tpuflow/utils/knobs.py``. Every knob read goes
  through the registry's typed accessors; a raw read bypasses the
  declaration check that makes typos die loudly. tests/ are exempt
  (chaos-test gang snippets exercise the raw plumbing deliberately —
  their literals are still covered by ``knob-undeclared``).
- ``knob-dynamic``      — an env read or knob accessor whose name
  argument is not a string literal: invisible to every static rule
  here. Needs a pragma with a justification where genuinely necessary
  (e.g. a helper forwarding a literal from its call sites).
- ``knob-undeclared``   — any exact ``TPUFLOW_*`` string literal (reads,
  writes, ``monkeypatch.setenv``, manifest env lists) naming a knob the
  registry does not declare. This is where a
  ``TPUFLOW_SERVE_PAGE``-style typo dies at lint time instead of
  silently defaulting.
- ``knob-readme-stale`` — the README's generated knob-table region is
  missing or does not match ``python -m tpuflow.utils.knobs
  --markdown`` byte-for-byte (every registry entry is documented in a
  README knob table, by construction of the generated region).
- ``knob-readme-unknown`` — the README mentions a ``TPUFLOW_*`` name the
  registry does not declare (prose drifting from code).
"""

from __future__ import annotations

import re

import ast

from tpuflow.lint.core import Sink, Tree, const_str, dotted

# The registry module itself, repo-relative: the one place raw reads live.
REGISTRY_FILE = "tpuflow/utils/knobs.py"

ACCESSORS = (
    "raw", "is_set", "get_str", "get_int", "get_float", "get_bool",
    "get_int_lenient", "get_float_lenient",
)

_NAME_RE = re.compile(r"^TPUFLOW_[A-Z0-9_]+$")
_README_TOKEN_RE = re.compile(r"TPUFLOW_[A-Z0-9_]+")


def _declared_names(registry=None) -> frozenset[str]:
    if registry is not None:
        return frozenset(registry)
    from tpuflow.utils.knobs import REGISTRY

    return frozenset(REGISTRY)


def _knob_literal(value: str) -> str | None:
    """Normalized declared-name candidate for an exact TPUFLOW_* string
    literal; None for non-knob strings. Trailing underscores are
    stripped so prefix literals (``"TPUFLOW_SERVE_"``) resolve to their
    base knob; the bare ``TPUFLOW_`` prefix is not a name."""
    if not _NAME_RE.match(value):
        return None
    name = value.rstrip("_")
    if name in ("TPUFLOW",):
        return None
    return name


def _is_environ(node: ast.AST) -> bool:
    d = dotted(node)
    return d is not None and (d == "environ" or d.endswith(".environ"))


def run(
    tree: Tree,
    registry=None,
    readme_rel: str | None = "README.md",
    check_readme: bool = True,
):
    declared = _declared_names(registry)
    sink = Sink(tree)

    for rel in tree.files():
        mod = tree.tree(rel)
        if mod is None:
            continue
        in_registry = rel.replace("\\", "/") == REGISTRY_FILE
        in_tests = rel.replace("\\", "/").startswith("tests/")
        in_tpuflow = rel.replace("\\", "/").startswith("tpuflow/")
        for node in ast.walk(mod):
            # ---- raw env reads -------------------------------------
            if isinstance(node, ast.Call):
                d = dotted(node.func) or ""
                is_env_get = (
                    d.endswith("environ.get") or d.endswith("os.getenv")
                    or d == "getenv"
                )
                if is_env_get and node.args:
                    name = const_str(node.args[0])
                    if name is None:
                        if in_tpuflow and not in_registry:
                            sink.emit(
                                rel, node.lineno, "knob-dynamic",
                                f"env read {d}(<non-literal>) — a "
                                "dynamic name is invisible to the "
                                "registry rules; read through "
                                "tpuflow.utils.knobs with a literal "
                                "name",
                            )
                    elif (
                        name.startswith("TPUFLOW_")
                        and not in_registry
                        and not in_tests
                    ):
                        sink.emit(
                            rel, node.lineno, "knob-raw-env",
                            f"raw env read of {name!r} bypasses the "
                            "knob registry — use tpuflow.utils.knobs "
                            "accessors",
                        )
                # ---- knob accessor calls ---------------------------
                if (
                    d.startswith("knobs.")
                    and d.split(".", 1)[1] in ACCESSORS
                    and node.args
                    and not in_registry
                    and not in_tests
                ):
                    name = const_str(node.args[0])
                    if name is None:
                        sink.emit(
                            rel, node.lineno, "knob-dynamic",
                            f"{d}(<non-literal>) — accessor names must "
                            "be string literals so the declared-name "
                            "rule can check them statically",
                        )
            # ---- environ subscript reads ---------------------------
            if (
                isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and _is_environ(node.value)
            ):
                name = const_str(node.slice)
                if (
                    name
                    and name.startswith("TPUFLOW_")
                    and not in_registry
                    and not in_tests
                ):
                    sink.emit(
                        rel, node.lineno, "knob-raw-env",
                        f"raw os.environ[{name!r}] read bypasses the "
                        "knob registry — use tpuflow.utils.knobs "
                        "accessors",
                    )
            # ---- membership reads ----------------------------------
            if isinstance(node, ast.Compare) and any(
                isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
            ):
                name = const_str(node.left)
                if (
                    name
                    and name.startswith("TPUFLOW_")
                    and any(_is_environ(c) for c in node.comparators)
                    and not in_registry
                    and not in_tests
                ):
                    sink.emit(
                        rel, node.lineno, "knob-raw-env",
                        f"raw `{name!r} in os.environ` check bypasses "
                        "the knob registry — use knobs.is_set",
                    )
            # ---- undeclared exact literals -------------------------
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                name = _knob_literal(node.value)
                if name is not None and name not in declared:
                    sink.emit(
                        rel, node.lineno, "knob-undeclared",
                        f"{node.value!r} is not declared in "
                        "tpuflow/utils/knobs.py — a typo'd knob name "
                        "silently defaults; declare it or fix the "
                        "spelling",
                    )

    # ---- README sync -------------------------------------------------
    if check_readme and readme_rel is not None:
        import os

        from tpuflow.utils import knobs as knobs_mod

        readme_path = os.path.join(tree.root, readme_rel)
        for err in knobs_mod.check_readme(readme_path):
            sink.emit(readme_rel, 1, "knob-readme-stale", err)
        try:
            with open(readme_path) as f:
                readme_text = f.read()
        except OSError:
            readme_text = ""
        seen = set()
        for i, line in enumerate(readme_text.split("\n"), start=1):
            for tok in _README_TOKEN_RE.findall(line):
                name = _knob_literal(tok)
                if name and name not in declared and name not in seen:
                    seen.add(name)
                    sink.emit(
                        readme_rel, i, "knob-readme-unknown",
                        f"README mentions {tok!r} but the registry does "
                        "not declare it — prose drifted from code",
                    )

    return sink.result()
