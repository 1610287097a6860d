"""Each kind of cell, for each family found on disk, driven on the CPU at the
`test` width: everything of a run but the look for a chip. A rehearsal ends in the contract's last line
and reports no device metric; with the timed path broken underneath,
`correct` comes out false; the float8 control fails the limits."""

import io
import json
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmark.harness import check, runner, traffic
from benchmark.tests import cells

PAIRS = cells.pairs()


def families_with(kind: str) -> list[str]:
    return [f for f, k in PAIRS if k == kind]


def rehearse(kind: str, family: str, seed: int = 2**31 + 11, trace: bool = False) -> dict:
    return runner.run_cell(
        cells.cell(kind, family), seed=seed, seconds=1.5, trace=trace,
        t_start=time.monotonic(), reach_chip_s=0.0, rehearse=True,
    )


def test_every_kind_on_disk_is_rehearsed_by_some_family():
    assert {k for _, k in PAIRS} == set(cells.KINDS)


@pytest.mark.parametrize("family,kind", PAIRS)
def test_rehearsal_ends_in_the_contract_line_without_device_metrics(family, kind):
    result = rehearse(kind, family, trace=(kind == "train_ckpt"))
    out = io.StringIO()
    with redirect_stdout(out):
        runner.emit(result)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert list(last)[-1] == "compared"  # the numbers compared come last
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"] and "breakdown" not in last
    for value, limit in last["compared"].values():
        assert value is not None and value <= limit


def _break_step(monkeypatch, how: str):
    """Plant a fault in the program's train step as the rig builds it."""
    import jax

    import tpuflow.train as train

    real = train.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def unchanged(state, batch, rng):
            _, metrics = jax.jit(lambda s, b, r: step(s, b, r), donate_argnums=())(
                state, batch, rng
            )
            return state, metrics

        def half(state, batch, rng):
            n = batch["x"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()}, rng)

        return {"unchanged": unchanged, "half": half}[how]

    monkeypatch.setattr(train, "make_train_step", broken)


@pytest.mark.parametrize("family", families_with("train_steady"))
@pytest.mark.parametrize("how,number", [("unchanged", "dparam_gap"), ("half", "grad_gap")])
def test_a_broken_train_step_is_not_correct(monkeypatch, how, number, family):
    _break_step(monkeypatch, how)
    result = rehearse("train_steady", family)
    assert result["correct"] is False
    value, limit = result["compared"][number]
    assert value > limit
    if how == "unchanged":
        assert value > 0.99  # a leaf that has not moved reads 1


@pytest.mark.parametrize("family", families_with("train_ckpt"))
def test_a_checkpoint_read_back_altered_is_not_correct(monkeypatch, family):
    import jax

    from tpuflow.ckpt import CheckpointManager

    real = CheckpointManager.restore

    def altered(self, step=None, **kw):
        tree = real(self, step, **kw)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        leaves[-1] = leaves[-1] + 1  # one leaf, where it is produced
        return jax.tree_util.tree_unflatten(treedef, leaves)

    monkeypatch.setattr(CheckpointManager, "restore", altered)
    result = rehearse("train_ckpt", family)
    assert result["correct"] is False
    assert result["compared"]["ckpt_leaves_mismatched"][0] > 0
    assert result["compared"]["grad_gap"][0] <= result["compared"]["grad_gap"][1]


def test_a_leaf_read_back_in_another_order_reads_another_checksum(tmp_path):
    import jax.numpy as jnp

    from benchmark.loops import train_ckpt

    a = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) * 65536.0  # low bits all nought
    tree = {"a": a, "rows": a[::-1], "one": a.at[3, 3].add(65536.0), "same": a + 0.0,
            "half": a.astype(jnp.bfloat16), "half_t": a.astype(jnp.bfloat16).T}
    sums = {k: int(v) for k, v in train_ckpt._checksums(tree).items()}
    assert sums["a"] == sums["same"]
    assert len({sums["a"], sums["rows"], sums["one"]}) == 3
    assert sums["half"] != sums["half_t"]
    for name in ("step_40", "step_60.tmp", "step_x", "r00000001.bin"):
        (tmp_path / name).mkdir()
    assert train_ckpt.committed_steps(str(tmp_path)) == {40}


@pytest.mark.parametrize("family", families_with("train_ckpt"))
def test_a_save_that_never_commits_is_missing(monkeypatch, family):
    from benchmark.loops import train_ckpt

    real = train_ckpt.committed_steps
    monkeypatch.setattr(train_ckpt, "committed_steps", lambda d: real(d) - {8})
    result = rehearse("train_ckpt", family)
    assert result["correct"] is False and result["failed"] == 1
    assert result["compared"]["ckpt_steps_missing"][0] == 1
    assert result["compared"]["ckpt_leaves_mismatched"][0] == 0


@pytest.mark.parametrize("family", families_with("serve_open_loop"))
def test_a_served_token_altered_is_not_correct(monkeypatch, family):
    from tpuflow.infer.serve import ServeEngine

    real = ServeEngine.step
    cell = cells.cell("serve_open_loop", family)
    vocab = cell["family"].vocabulary(cell["config"]["model"])

    hit = set()

    def altered(self, admit=True):
        did = real(self, admit)
        for req in self._slots:
            if req is not None and len(req.tokens) >= 3 and req.id not in hit:
                hit.add(req.id)
                req.tokens[2] = (req.tokens[2] + 7) % vocab
        return did

    monkeypatch.setattr(ServeEngine, "step", altered)
    result = rehearse("serve_open_loop", family)
    assert result["correct"] is False
    value, limit = result["compared"]["widest_logit_gap"]
    assert value > limit


@pytest.mark.parametrize("family", families_with("serve_open_loop"))
def test_a_request_that_never_finishes_fails_and_is_not_correct(monkeypatch, family):
    from tpuflow.infer.serve import ServeEngine

    real = ServeEngine.submit
    cell = cells.cell("serve_open_loop", family)
    cell["traffic"]["drain_s"] = 1.0

    def lose_one(self, prompt, **kw):
        req = real(self, prompt, **kw)
        if req.id == 9:
            self._queue.remove(req)  # accepted, then never served
        return req

    monkeypatch.setattr(ServeEngine, "submit", lose_one)
    result = runner.run_cell(cell, seed=5, seconds=1.5, trace=False,
                             t_start=time.monotonic(), rehearse=True)
    assert result["failed"] == 1 and result["correct"] is False


@pytest.mark.parametrize("family", families_with("train_steady"))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_and_half_a_batch_fail_the_limits(seed, family):
    cell = cells.cell("train_steady", family)
    fam, m, opt = cell["family"], cell["config"]["model"], cell["config"]["optimizer"]
    corpus = traffic.lm_corpus(seed, 64, 64, fam.vocabulary(m))
    batches = [(corpus[i * 4:(i + 1) * 4, :-1], corpus[i * 4:(i + 1) * 4, 1:]) for i in range(3)]
    ref = fam.train_reference(m, opt, seed, batches, rows_per_block=2)
    again = fam.train_reference(m, opt, seed, batches, rows_per_block=4)
    ok, _ = check.judge(check.compare_train(again, ref), cell["limits"])
    assert ok  # the reference agrees with itself whatever the row blocks
    witness = fam.train_reference(m, opt, seed, batches, rows_per_block=2, quant="bf16")
    ok, compared = check.judge(check.compare_train(witness, ref), cell["limits"])
    assert ok, compared  # rounding at the stated precision is no control: it passes
    for kw in ({"quant": "fp8"}, {"fault": "half_batch"}):
        other = fam.train_reference(m, opt, seed, batches, rows_per_block=2, **kw)
        ok, compared = check.judge(check.compare_train(other, ref), cell["limits"])
        assert not ok, (kw, compared)


@pytest.mark.parametrize("family", families_with("serve_open_loop"))
def test_the_served_control_reads_a_wider_gap_than_the_reference_itself(family):
    import jax
    import jax.numpy as jnp

    from benchmark.harness.reference import seed_key

    cell = cells.cell("serve_open_loop", family)
    fam, m = cell["family"], cell["config"]["model"]
    vocab, positions = fam.vocabulary(m), fam.positions(m)
    rng = np.random.default_rng(0)
    params = jax.jit(lambda k: fam.make_params(m, k))(seed_key(4))
    prompt = rng.integers(1, vocab, size=40)
    seq = list(prompt)
    for _ in range(24):  # greedy tokens of the reference itself
        logits = fam.forward_logits(params, jnp.asarray([seq]), m)[0, -1]
        seq.append(int(jnp.argmax(logits)))
    got = fam.serve_gaps(m, 4, [(prompt, np.array(seq[40:]))])
    assert got["tokens"] == 24 and got["widest_gap"] < 1e-5
    # the control's first choice, over many positions, is not always the reference's
    rows = [(r[:1], r[1:]) for r in rng.integers(1, vocab, size=(4, positions))]
    low = fam.serve_gaps(m, 4, rows, quant="fp8")
    assert low["tokens"] == 4 * (positions - 1) and low["widest_gap_low"] > 1e-4
