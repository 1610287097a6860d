"""tpulint (ISSUE 12): the knob registry and the four lint passes.

All fast-tier and jax-free: the passes are pure AST walks, the fixtures
are tiny snippet files under tmp_path, and the tree-green twins run the
real passes over the repository exactly as ``python tools/tpulint.py``
does — the pytest twin that makes the lint a tier-1 gate beside the
obs_lint twin.

Fixture discipline: every rule has a seeded-violation snippet proving it
FIRES and a clean snippet proving it stays quiet — a lint that can't
fail is indistinguishable from no lint.

NOTE: undeclared-name fixtures build their knob strings by
concatenation ("TPUFLOW_" "..." would itself be an exact literal this
file's tree scan would flag).
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpuflow.lint import core, jit_pass, knob_pass, obs_pass, recompile_pass  # noqa: E402
from tpuflow.utils import knobs  # noqa: E402


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)
    return path


def _tree(root, scan=("tpuflow", "tools")):
    return core.Tree(str(root), scan=scan)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ===================================================== knob registry
def test_registry_round_trip_typed_accessors(monkeypatch):
    """Typed accessors parse set values and fall back to registry
    defaults; raw() is byte-faithful; undeclared names die loudly."""
    monkeypatch.delenv("TPUFLOW_DISPATCH_DEPTH", raising=False)
    assert knobs.get_int("TPUFLOW_DISPATCH_DEPTH") == 2  # registry default
    monkeypatch.setenv("TPUFLOW_DISPATCH_DEPTH", "5")
    assert knobs.get_int("TPUFLOW_DISPATCH_DEPTH") == 5
    assert knobs.raw("TPUFLOW_DISPATCH_DEPTH") == "5"
    assert knobs.is_set("TPUFLOW_DISPATCH_DEPTH")

    monkeypatch.setenv("TPUFLOW_CKPT_IO_BACKOFF_S", "0.25")
    assert knobs.get_float("TPUFLOW_CKPT_IO_BACKOFF_S") == 0.25
    monkeypatch.delenv("TPUFLOW_CKPT_IO_BACKOFF_S", raising=False)
    assert knobs.get_float("TPUFLOW_CKPT_IO_BACKOFF_S") == 0.05

    # bool convention: truthy unless 0/false/off/no (the comm-overlap
    # semantics pinned in test_dispatch).
    monkeypatch.delenv("TPUFLOW_COMM_OVERLAP", raising=False)
    assert knobs.get_bool("TPUFLOW_COMM_OVERLAP") is True
    for falsy in ("0", "false", "OFF", "no"):
        monkeypatch.setenv("TPUFLOW_COMM_OVERLAP", falsy)
        assert knobs.get_bool("TPUFLOW_COMM_OVERLAP") is False
    monkeypatch.setenv("TPUFLOW_COMM_OVERLAP", "weird")
    assert knobs.get_bool("TPUFLOW_COMM_OVERLAP") is True

    # call-site default beats registry default only when given
    monkeypatch.delenv("TPUFLOW_SERVE_SLOTS", raising=False)
    assert knobs.get_int("TPUFLOW_SERVE_SLOTS", 3) == 3
    assert knobs.get_int("TPUFLOW_SERVE_SLOTS") == 8

    with pytest.raises(KeyError, match="undeclared"):
        knobs.raw("TPUFLOW_" + "NO_SUCH_KNOB")
    with pytest.raises(KeyError, match="undeclared"):
        knobs.get_int("TPUFLOW_" + "NO_SUCH_KNOB")


def test_registry_lenient_accessors(monkeypatch):
    """Malformed values fall back instead of raising — the
    dispatch-depth idiom the lenient accessors exist for."""
    monkeypatch.setenv("TPUFLOW_PREFETCH_DEPTH", "not-an-int")
    assert knobs.get_int_lenient("TPUFLOW_PREFETCH_DEPTH") == 2
    assert knobs.get_int_lenient("TPUFLOW_PREFETCH_DEPTH", 7) == 7
    monkeypatch.setenv("TPUFLOW_PREFETCH_DEPTH", "4")
    assert knobs.get_int_lenient("TPUFLOW_PREFETCH_DEPTH") == 4
    monkeypatch.setenv("TPUFLOW_HEALTH_SPIKE_MADS", "nope")
    assert knobs.get_float_lenient("TPUFLOW_HEALTH_SPIKE_MADS") == 12.0
    # strict accessors DO raise on the same input, naming the knob
    with pytest.raises(ValueError, match="TPUFLOW_PREFETCH_DEPTH"):
        monkeypatch.setenv("TPUFLOW_PREFETCH_DEPTH", "zz")
        knobs.get_int("TPUFLOW_PREFETCH_DEPTH")


def test_registry_defaults_match_declared_types():
    """Every declared default round-trips through its own type — a
    registry entry whose default can't parse would turn the typed
    accessors into landmines."""
    for k in knobs.REGISTRY.values():
        if k.default is None:
            continue
        if k.type == "int":
            assert isinstance(k.default, int) and not isinstance(
                k.default, bool
            ), k.name
        elif k.type == "float":
            assert isinstance(k.default, (int, float)), k.name
        elif k.type == "bool":
            assert isinstance(k.default, bool), k.name
        elif k.type == "enum":
            assert k.choices, k.name
            assert k.default in k.choices, k.name


def test_registry_markdown_covers_every_knob():
    md = knobs.markdown()
    for name in knobs.REGISTRY:
        assert f"`{name}`" in md, f"{name} missing from generated tables"
    assert md.startswith(knobs.MARKDOWN_BEGIN)
    assert md.endswith(knobs.MARKDOWN_END)


def test_knobs_check_mode(tmp_path):
    """--check: in-sync README passes, stale/missing README fails."""
    good = tmp_path / "README.md"
    good.write_text("# x\n\n" + knobs.markdown() + "\n\ntail\n")
    assert knobs.check_readme(str(good)) == []
    stale = tmp_path / "stale.md"
    stale.write_text(
        "# x\n\n" + knobs.markdown().replace("| int |", "| str |", 1)
        + "\n"
    )
    assert any("stale" in e for e in knobs.check_readme(str(stale)))
    missing = tmp_path / "none.md"
    missing.write_text("# no markers\n")
    assert any("markers" in e for e in knobs.check_readme(str(missing)))


def test_knobs_cli_check_real_readme():
    """The committed README's generated region is in sync (the same
    check pass 1 runs; standalone so the failure message is direct)."""
    rc = subprocess.run(
        [sys.executable, "-m", "tpuflow.utils.knobs", "--check"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert rc.returncode == 0, rc.stdout + rc.stderr


# ===================================================== pass 1: knobs
_KNOB_BAD = """
import os
from tpuflow.utils import knobs

a = os.environ.get("TPUFLOW_DISPATCH_DEPTH", "2")
b = os.environ["TPUFLOW_HOME"]
c = "TPUFLOW_FAULT" in os.environ
d = os.environ.get("TPU" + "FLOW_DYN")
e = knobs.raw("TPUFLOW_TYPOD_KNOB")
"""

_KNOB_CLEAN = """
import os
from tpuflow.utils import knobs

a = knobs.raw("TPUFLOW_DISPATCH_DEPTH", "2")
b = knobs.get_str("TPUFLOW_HOME")
c = knobs.is_set("TPUFLOW_FAULT")
os.environ["TPUFLOW_ATTEMPT"] = "1"  # writes stay allowed
jaxy = os.environ.get("JAX_PLATFORMS")  # non-TPUFLOW reads untouched
"""


def test_knob_pass_fires_on_seeded_violations(tmp_path):
    _write(tmp_path, "tpuflow/mod.py", _KNOB_BAD)
    found = knob_pass.run(_tree(tmp_path), readme_rel=None)
    rules = _rules(found)
    assert "knob-raw-env" in rules
    assert "knob-dynamic" in rules
    assert "knob-undeclared" in rules
    # the raw .get, the subscript, and the membership check all fire
    raw_lines = [f.line for f in found if f.rule == "knob-raw-env"]
    assert len(raw_lines) >= 3


def test_knob_pass_clean_snippet_passes(tmp_path):
    _write(tmp_path, "tpuflow/mod.py", _KNOB_CLEAN)
    assert knob_pass.run(_tree(tmp_path), readme_rel=None) == []


def test_knob_pass_registry_param_and_tests_scope(tmp_path):
    """Custom registries narrow the declared set; tests/ are exempt
    from the raw-read ban but not from the undeclared-literal rule."""
    _write(
        tmp_path, "tests/test_x.py",
        'import os\nv = os.environ.get("TPUFLOW_DISPATCH_DEPTH")\n'
        'w = "TPUFLOW_MADE_UP_NAME"\n',
    )
    found = knob_pass.run(
        core.Tree(str(tmp_path), scan=("tests",)),
        registry={"TPUFLOW_DISPATCH_DEPTH"},
        readme_rel=None,
    )
    rules = _rules(found)
    assert "knob-raw-env" not in rules  # tests may read raw env
    assert "knob-undeclared" in rules  # but literals must be declared


def test_knob_pass_readme_rules(tmp_path):
    _write(tmp_path, "tpuflow/mod.py", "x = 1\n")
    _write(
        tmp_path, "README.md",
        "# doc\n\nmentions TPUFLOW_NOT_A_REAL_NAME here\n",
    )
    found = knob_pass.run(_tree(tmp_path), readme_rel="README.md")
    rules = _rules(found)
    assert "knob-readme-stale" in rules  # no generated region
    assert "knob-readme-unknown" in rules  # undeclared prose mention
    # in-sync README with only declared names is quiet
    _write(
        tmp_path, "README2.md",
        "# doc\n\n" + knobs.markdown() + "\n",
    )
    assert (
        knob_pass.run(_tree(tmp_path), readme_rel="README2.md") == []
    )


def test_pragma_requires_justification(tmp_path):
    justified = (
        "import os\n"
        "# tpulint: disable=knob-raw-env -- fixture proves the escape "
        "hatch\n"
        'v = os.environ.get("TPUFLOW_DISPATCH_DEPTH")\n'
    )
    _write(tmp_path, "tpuflow/ok.py", justified)
    assert knob_pass.run(_tree(tmp_path), readme_rel=None) == []

    bare = (
        "import os\n"
        "# tpulint: disable=knob-raw-env\n"
        'v = os.environ.get("TPUFLOW_DISPATCH_DEPTH")\n'
    )
    _write(tmp_path, "tpuflow/ok.py", bare)
    found = knob_pass.run(_tree(tmp_path), readme_rel=None)
    assert _rules(found) == ["pragma-justification"]


# ======================================================= pass 2: jit
_JIT_BAD = """
import os
import time
import random
import functools
import jax
from tpuflow.utils import knobs


def traced(state, batch):
    depth = os.environ.get("TPUFLOW_DISPATCH_DEPTH", "2")
    k = knobs.raw("TPUFLOW_SERVE_SLOTS")
    t = time.monotonic()
    r = random.random()
    host = batch.tolist()
    f = float(state)
    return state


step = jax.jit(traced, donate_argnums=(0, 1))


def loop(state, batch):
    out = step(state, batch)
    again = state  # donated operand read after the call
    return out, again
"""

_JIT_CLEAN = """
import functools
import jax


def traced(state, batch):
    return state, batch.sum()


step = jax.jit(traced, donate_argnums=(0,))


@functools.partial(jax.jit, donate_argnums=(0,))
def other(opt_state, x):
    return opt_state


def loop(state, batch):
    state, loss = step(state, batch)
    return state, loss
"""


def test_jit_pass_fires_on_seeded_violations(tmp_path):
    _write(tmp_path, "tpuflow/mod.py", _JIT_BAD)
    rules = _rules(jit_pass.run(_tree(tmp_path)))
    for rule in (
        "jit-env-read", "jit-time", "jit-host-rng", "jit-host-sync",
        "jit-donate-nonstate", "jit-donate-reuse",
    ):
        assert rule in rules, rule


def test_jit_pass_clean_snippet_passes(tmp_path):
    _write(tmp_path, "tpuflow/mod.py", _JIT_CLEAN)
    assert jit_pass.run(_tree(tmp_path)) == []


def test_jit_pass_partial_binding_shifts_donation(tmp_path):
    """functools.partial-bound leading args shift donate positions the
    way ServeEngine's decode programs use them: donate_argnums=(1,) on
    partial(fn, model) donates fn's `cache`, which is fine — but
    donating the partial's arg 0 (`batch_like`) is flagged."""
    src = (
        "import functools\n"
        "import jax\n\n\n"
        "class Engine:\n"
        "    def _decode_fn(self, model, params, cache, tok):\n"
        "        return cache, tok\n\n"
        "    def build(self, model):\n"
        "        self._decode = jax.jit(\n"
        "            functools.partial(self._decode_fn, model),\n"
        "            donate_argnums=(1,),\n"
        "        )\n"
    )
    _write(tmp_path, "tpuflow/mod.py", src)
    assert jit_pass.run(_tree(tmp_path)) == []
    bad = src.replace("donate_argnums=(1,)", "donate_argnums=(2,)")
    bad = bad.replace("cache, tok", "cache, batch_like").replace(
        "return cache, batch_like", "return cache, batch_like"
    )
    _write(tmp_path, "tpuflow/mod.py", bad)
    rules = _rules(jit_pass.run(_tree(tmp_path)))
    assert "jit-donate-nonstate" in rules


def test_jit_pass_rebind_same_statement_is_not_reuse(tmp_path):
    """self._cache = self._insert(self._cache, ...) — the serve idiom:
    same-statement rebinding of a donated attribute is legal."""
    src = (
        "import jax\n\n\n"
        "class Engine:\n"
        "    def _page_insert_fn(self, cache, row):\n"
        "        return cache\n\n"
        "    def build(self):\n"
        "        self._insert = jax.jit(\n"
        "            self._page_insert_fn, donate_argnums=(0,)\n"
        "        )\n\n"
        "    def admit(self, row):\n"
        "        self._cache = self._insert(self._cache, row)\n"
        "        return self._cache\n"
    )
    _write(tmp_path, "tpuflow/mod.py", src)
    assert jit_pass.run(_tree(tmp_path)) == []


# ================================================= pass 3: recompile
_SERVE_OK = """
import jax


class ServeEngine:
    def __init__(self):
        self._decode = jax.jit(self._decode_fn, donate_argnums=(0,))
        self._prefill = jax.jit(self._prefill_fn)

    def _decode_fn(self, cache):
        return cache

    def _prefill_fn(self, x):
        return x

    def compile_stats(self):
        return {
            "decode": self._decode._cache_size(),
            "prefill": self._prefill._cache_size(),
        }

    def warmup(self):
        self._cache = self._decode(self._cache)
        self._prefill(0)

    def aot_lower(self):
        self._decode.lower(self._cache).compile()
        self._prefill.lower(0).compile()
        return 2
"""

_PREWARM_OK = """
def prewarm(engine):
    return engine.aot_lower()
"""


def _recompile(tmp_path):
    return recompile_pass.run(
        _tree(tmp_path),
        serve_rel="tpuflow/serve_fixture.py",
        prewarm_rel="tools/prewarm_fixture.py",
    )


def test_recompile_pass_clean_engine_passes(tmp_path):
    _write(tmp_path, "tpuflow/serve_fixture.py", _SERVE_OK)
    _write(tmp_path, "tools/prewarm_fixture.py", _PREWARM_OK)
    assert _recompile(tmp_path) == []


def test_recompile_pass_fires_on_uncovered_program(tmp_path):
    """A new jit program missing from any coverage surface fails —
    the drifted-tool scenario pass 3 exists to kill."""
    bad = _SERVE_OK.replace(
        "        self._prefill = jax.jit(self._prefill_fn)\n",
        "        self._prefill = jax.jit(self._prefill_fn)\n"
        "        self._verify = jax.jit(self._decode_fn)\n",
    )
    _write(tmp_path, "tpuflow/serve_fixture.py", bad)
    _write(tmp_path, "tools/prewarm_fixture.py", _PREWARM_OK)
    found = _recompile(tmp_path)
    assert any(
        f.rule == "serve-aot-coverage" and "_verify" in f.message
        for f in found
    )
    # one finding per missing surface: stats, warmup, aot_lower
    assert len([f for f in found if "_verify" in f.message]) == 3


def test_recompile_pass_fires_on_prewarm_drift(tmp_path):
    _write(tmp_path, "tpuflow/serve_fixture.py", _SERVE_OK)
    _write(
        tmp_path, "tools/prewarm_fixture.py",
        "def prewarm(engine):\n    return 0  # hand-rolled list\n",
    )
    found = _recompile(tmp_path)
    assert any(
        f.rule == "serve-aot-coverage" and "aot_lower" in f.message
        for f in found
    )


# ======================================================= pass 4: obs
_CATALOG = {
    "x.good": ("span", "fixture"),
    "x.unused": ("gauge", "fixture"),
}

_OBS_BAD = """
from tpuflow import obs

with obs.span("x.good"):
    pass
obs.counter("x.good")        # kind mismatch
obs.event("x.rogue")         # unregistered
name = "x.dyn"
obs.gauge(name, 1)           # dynamic
"""


def test_obs_pass_fires_on_seeded_violations(tmp_path):
    _write(tmp_path, "tpuflow/mod.py", _OBS_BAD)
    found = obs_pass.run(
        _tree(tmp_path), catalog=_CATALOG, required=(),
        duration_guard=False,
    )
    rules = _rules(found)
    for rule in (
        "obs-kind-mismatch", "obs-unregistered", "obs-dynamic-name",
        "obs-unemitted",
    ):
        assert rule in rules, rule


def test_obs_pass_unemitted_promotion_and_grandfather(tmp_path):
    """The ISSUE 12 satellite: unemitted catalog entries are errors now;
    the explicit grandfather list is the only escape."""
    _write(
        tmp_path, "tpuflow/mod.py",
        'from tpuflow import obs\n\nwith obs.span("x.good"):\n    pass\n',
    )
    found = obs_pass.run(
        _tree(tmp_path), catalog=_CATALOG, required=(),
        duration_guard=False,
    )
    assert _rules(found) == ["obs-unemitted"]
    assert "x.unused" in found[0].message
    assert (
        obs_pass.run(
            _tree(tmp_path), catalog=_CATALOG, required=(),
            grandfather=frozenset({"x.unused"}), duration_guard=False,
        )
        == []
    )


def test_obs_pass_required_emitters(tmp_path):
    _write(
        tmp_path, "tpuflow/mod.py",
        'from tpuflow import obs\n\nwith obs.span("x.good"):\n    pass\n'
        "obs.gauge(\"x.unused\", 1)\n",
    )
    found = obs_pass.run(
        _tree(tmp_path), catalog=_CATALOG,
        required=(("event", "x.never"),), duration_guard=False,
    )
    assert _rules(found) == ["obs-missing-required"]


def test_obs_pass_grandfather_list_is_empty():
    """Burned down and must stay that way — stage names and emitters in
    the same PR."""
    assert obs_pass.UNEMITTED_GRANDFATHER == frozenset()


# ================================================== tree-green twins
def test_tpulint_tree_green():
    """The pytest twin of `python tools/tpulint.py`: all four passes,
    shared AST walk, zero findings on the committed tree. This is the
    tier-1 gate that makes every contract above a review-time failure."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpulint", os.path.join(REPO, "tools", "tpulint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    findings = mod.lint(REPO)
    assert not findings, "\n".join(str(f) for f in findings)


def test_tpulint_cli_pass_selection(tmp_path):
    """The standalone CLI exits nonzero on a violating tree and 0 on
    the committed one (single-pass selection keeps it cheap)."""
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpulint.py"),
         "--pass", "recompile"],
        capture_output=True, text=True,
    )
    assert rc.returncode == 0, rc.stdout + rc.stderr
    _write(tmp_path, "tpuflow/infer/serve.py", "x = 1\n")
    _write(tmp_path, "tools/prewarm_cache.py", "y = 2\n")
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpulint.py"),
         "--pass", "recompile", "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert rc.returncode == 1
    assert "serve-aot-coverage" in rc.stdout


def test_no_raw_tpuflow_env_reads_outside_registry():
    """The acceptance criterion, stated directly: zero raw TPUFLOW_*
    env reads outside tpuflow/utils/knobs.py (tests exempt — their gang
    snippets exercise the raw plumbing deliberately)."""
    tree = core.Tree(REPO)
    found = [
        f for f in knob_pass.run(tree, check_readme=False)
        if f.rule in ("knob-raw-env", "knob-dynamic")
    ]
    assert not found, "\n".join(str(f) for f in found)


def test_every_registered_knob_is_named_by_code():
    """A knob the registry declares and no code names is a table row for
    nothing: every name appears in some Python file under tpuflow/,
    flows/, tools/, tests/ or in chip_smoke.py, other than the registry
    itself. (A name that is a prefix of another, as
    ``TPUFLOW_SERVE`` is of ``TPUFLOW_SERVE_SLOTS``, must appear whole.)"""
    import re

    registry = os.path.join("tpuflow", "utils", "knobs.py")
    tree = core.Tree(REPO, scan=core.DEFAULT_SCAN + ("chip_smoke.py",))
    seen: set[str] = set()
    for rel in tree.files():
        if rel != registry:
            seen.update(
                re.findall(r"TPUFLOW_[A-Z0-9_]*[A-Z0-9]", tree.source(rel))
            )
    unnamed = sorted(set(knobs.REGISTRY) - seen)
    assert not unnamed, f"declared in {registry}, named by no code: {unnamed}"
