"""What the two training kinds share: the program's own calls, in the order
`train/gpt.py:_run_fsdp_generation` makes them, fed by the benchmark's
weights and corpus, and the first three steps read for `correct`. The
module, the weights and the reference are the cell's family's."""

from __future__ import annotations

import itertools
import time

import numpy as np

from benchmark.harness import check, traffic
from benchmark.harness.reference import seed_key
from benchmark.harness.runner import log

CHECK_STEPS = 3


class TrainRig:
    """The compiled step with its state, its feed, and the readings of its
    first steps. One object: set-up drives it and the window goes on with it."""

    def __init__(self, cell: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from tpuflow import dist
        from tpuflow.data.datasets import Split
        from tpuflow.data.loader import ShardedLoader, prefetch_to_device
        from tpuflow.parallel import create_sharded_state
        from tpuflow.train import TrainState, make_optimizer, make_train_step
        from tpuflow.train.step import dispatch_depth

        cfg, tr = cell["config"], cell["traffic"]
        self.family = fam = cell["family"]
        self.m = m = cfg["model"]
        self.opt = cfg["optimizer"]
        self.seed = seed
        self.batch, self.seq = int(tr["batch_size"]), int(tr["seq_len"])
        self.depth = dispatch_depth()
        model = fam.module(m)
        tx = make_optimizer(**self.opt)
        self.mesh = dist.make_mesh({"data": 1, "fsdp": len(jax.devices())})

        def init_fn(key):
            return TrainState.create(
                apply_fn=model.apply, params=fam.make_params(m, key), tx=tx
            )

        declared = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0),
        )
        made = jax.eval_shape(lambda k: fam.make_params(m, k), jax.random.PRNGKey(0))
        if jax.tree_util.tree_map(lambda a: a.shape, declared) != jax.tree_util.tree_map(
            lambda a: a.shape, made
        ):
            raise RuntimeError("the program's parameter tree is not the benchmark's")
        t0 = time.monotonic()
        with self.mesh:
            self.state, self.shardings = create_sharded_state(
                init_fn, self.mesh, seed_key(seed), fsdp=True
            )
            jax.block_until_ready(self.state.params)
        log(f"state on the device in {time.monotonic() - t0:.1f}s")
        self.corpus = traffic.lm_corpus(seed, int(tr["corpus_rows"]), self.seq, fam.vocabulary(m))
        self.loader = ShardedLoader(
            Split(self.corpus[:, :-1], self.corpus[:, 1:]),
            batch_size=self.batch, shuffle=True, seed=seed,
        )
        sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(("data", "fsdp"), None)
        )
        self.fed: list[np.ndarray] = []  # the first batches as the loader gave them

        def place(b):
            if len(self.fed) < CHECK_STEPS:
                self.fed.append(np.array(b["x"]))
            return {k: jax.device_put(b[k], sharding) for k in ("x", "y")}

        def feed():
            for epoch in itertools.count():
                self.loader.set_epoch(epoch)
                yield from prefetch_to_device(
                    self.loader, self.mesh, keys=("x", "y"), place=place
                )

        self.feed = feed()
        self.train_step = make_train_step()
        self.rng = jax.random.PRNGKey(1)
        self.steps_done = 0
        self.last_loss = None

    def dispatch(self):
        """One step through the program's own call and feed; returns the
        handle whose readiness fences it."""
        with self.mesh:
            self.state, metrics = self.train_step(self.state, next(self.feed), self.rng)
        self.steps_done += 1
        self.last_loss = metrics["loss"]
        return metrics["loss"]

    @staticmethod
    def fence(handle) -> None:
        import jax

        jax.block_until_ready(handle)

    def first_steps(self) -> dict:
        """Steps 1 to 3, each fenced: every loss, the per-leaf norm of the
        first gradient as Adam got it (its first moment after one step is
        (1 - b1) times it), and the per-leaf norm of the parameters' change
        after the third, before step 4 takes the state."""
        losses, grad_norms = [], None
        for step in range(1, CHECK_STEPS + 1):
            losses.append(float(self.dispatch()))
            if step == 1:
                mu = next(s for s in self.state.opt_state if hasattr(s, "mu")).mu
                grad_norms = self.family.leaf_norms(mu, self.m, scale=1.0 / (1.0 - 0.9))
        dparam = self.family.delta_norms(self.state.params, self.m, self.seed)
        return {"losses": losses, "grad_norms": grad_norms, "dparam_norms": dparam}

    def batches_for_reference(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """The first batches for the reference: inputs as the loader fed
        them, every row found in the benchmark's corpus and all rows of a
        batch different, targets taken from the corpus itself."""
        index = {row[:-1].tobytes(): i for i, row in enumerate(self.corpus)}
        out = []
        for x in self.fed[:CHECK_STEPS]:
            rows = [index.get(r.tobytes()) for r in x]
            if None in rows or len(set(rows)) != len(rows):
                raise RuntimeError("a fed row is not a corpus row, or rows repeat")
            out.append((x, self.corpus[rows, 1:]))
        return out

    def free(self) -> None:
        self.state = None
        self.feed.close()


def judge_train(rig: TrainRig, prog: dict, cell: dict, extra: dict | None = None):
    """Run the reference over the same first steps (the program's state is
    freed by now) and hold every number to its limit."""
    limits = cell["limits"]
    t0 = time.monotonic()
    ref = rig.family.train_reference(
        rig.m, rig.opt, rig.seed, rig.batches_for_reference(),
        rows_per_block=int(cell["traffic"].get("reference_rows_per_block", 1)),
    )
    numbers = check.compare_train(prog, ref)
    numbers.update(extra or {})
    log(f"reference followed {CHECK_STEPS} steps in {time.monotonic() - t0:.1f}s: {numbers}")
    return check.judge(numbers, limits)
