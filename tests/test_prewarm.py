"""Compile-cache prewarm (ISSUE 9 startup-latency satellite):
``tools/prewarm_cache.py`` AOT-lowers the run's signatures straight into
the ONE persistent cache directory the run reads
(``dist.maybe_enable_compile_cache``) — JAX keys every entry on the
directory, so there is no copying between directories."""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_prewarm_writes_only_where_the_run_reads(monkeypatch):
    """No --cache-dir: the directory comes from the same function the run
    calls. And where that function keeps the cache off (CPU without
    --allow-cpu) the tool refuses instead of compiling into nothing."""
    spec = importlib.util.spec_from_file_location(
        "prewarm_cache", os.path.join(REPO, "tools", "prewarm_cache.py")
    )
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    with pytest.raises(SystemExit):
        tool._parse(["--cache-dir", "/somewhere/else"])
    monkeypatch.delenv("TPUFLOW_COMPILE_CACHE_CPU", raising=False)
    with pytest.raises(SystemExit, match="disabled"):
        tool.prewarm(tool._parse(["--no-train", "--no-serve"]))


@pytest.mark.slow
def test_prewarm_tool_populates_cache_end_to_end(tmp_path):
    """The tool AOT-compiles the train-step + serving signatures (fp AND
    the int8 twin, the paged decode block + page insert, and the
    speculative verify pair) into the placed cache dir WITHOUT executing a
    step — run in a subprocess because force-enabling the persistent
    cache on CPU must not leak into this test process (the XLA:CPU AOT
    reloader is the documented SIGABRT risk maybe_enable_compile_cache
    guards)."""
    cache = tmp_path / "prewarm"
    cache.mkdir()
    out = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "prewarm_cache.py"),
            "--preset", "test", "--batch", "2", "--seq-len", "32",
            "--buckets", "8", "--slots", "2",
            "--decode-block", "2", "--quant",
            "--spec", "2", "--page-size", "8",
            "--allow-cpu",
        ],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(cache)},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    entries = [p for p in cache.iterdir() if p.is_file()]
    assert entries, "prewarm wrote no cache entries"
    # fp + int8 serving programs and the train step all lowered:
    # 1 train step + 2 decodes + 2 verify blocks + 2 prefills (one
    # bucket) + 1 page insert (ServeEngine.aot_lower owns the list).
    import json

    rec = json.loads(out.stdout.splitlines()[0])
    assert rec["programs_compiled"] == 8
    assert rec["cache_entries"] == len(entries)
    assert rec["cache_dir"] == str(cache)
