"""Tier 1 runs the SDAR family's benchmark tests too (ISSUE 37 asks that
the cell's configuration check, its readers and the rehearsals of the
serving loop be guarded here, not only by `python -m pytest
benchmark/tests`): the tests are `benchmark/tests/test_sdar_family.py`'s,
collected again under this directory's conftest."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_sdar_family import *  # noqa: E402,F401,F403

import json  # noqa: E402

from benchmark.harness import manifest  # noqa: E402
from benchmark.tests.test_sdar_family import CELL  # noqa: E402


def test_the_cell_lists_the_accepted_readers_that_apply_and_its_own_two(cell):  # noqa: F811
    """The accepted file's test of this name, with the cell's own two
    metrics, the cell and its configuration found by name where it took
    them from the ends of their lists: the manifest puts each later PR's
    after them (PERF.md §7 (4))."""
    names = [m["name"] for m in cell["per_layer"]]
    own = ["tokens_per_pass.serve", "commit_pass_share.serve"]
    assert set(own) <= set(names)
    assert {"mfu.serve", "decode_bw_share.serve", "decode_carry_share.serve", "moe_share.serve",
            "moe_experts_roofline.serve", "experts_touched_share.serve"} <= set(names)
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_p90_ms", "setup_s"]
    man = manifest.load_manifest()
    for m in man["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p90_ms"
    # Found by name: a later PR's cell and configuration come after them.
    assert CELL in [w["name"] for w in man["workloads"]]
    assert cell["config_name"] in [c["name"] for c in man["configs"]]
    with open(os.path.join(manifest.BENCH_DIR, "limits", CELL + ".json")) as f:
        assert set(json.load(f)["limits"]) == {
            "widest_logit_gap", "requests_failed", "compiled_in_window"}
    fam = cell["family"]
    assert {"attn_core", "kv_read", "kv_write", "moe_experts", "router", "lm_head"} <= set(fam.BLOCK_SCOPES)
