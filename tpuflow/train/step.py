"""Jitted train/eval steps — the innermost hot loop.

TPU-native replacement for the reference's per-minibatch loop
(my_ray_module.py:153-175): one compiled ``train_step(state, batch, rng)``
where the data-parallel gradient all-reduce is emitted by GSPMD over ICI
(because the batch is sharded on the 'data' mesh axis while params are
replicated or FSDP-sharded) — there is no DDP wrapper and no explicit
collective call, matching the reference's encapsulation of NCCL behind
``prepare_model`` (my_ray_module.py:135).
"""

from __future__ import annotations

import collections
import os
import time
from typing import Any, Callable

import flax
import jax
import jax.numpy as jnp
from flax.training import train_state

from tpuflow import obs
from tpuflow.models.losses import accuracy, cross_entropy_loss
from tpuflow.obs import device as _device
from tpuflow.obs import goodput as _goodput
from tpuflow.obs import profcap as _profcap
from tpuflow.utils.heartbeat import beat as _heartbeat

# Preemption surface of the train layer (ISSUE 2): gang_exec installs the
# SIGTERM handler; the epoch loops check ``preemption_requested()`` at step
# boundaries, drain + commit a final checkpoint, and raise ``Preempted`` —
# which gang_exec converts into REQUEUE_EXIT_CODE so the flow supervisor
# reruns the step without consuming the @retry budget.
from tpuflow.utils.preempt import (  # noqa: F401  (re-exported API)
    REQUEUE_EXIT_CODE,
    Preempted,
    clear_preemption,
    emergency_save_advised,
    grace_remaining_s,
    install_sigterm_handler,
    preemption_requested,
    request_preemption,
)

# Elastic gang (ISSUE 7): the mesh re-form control-flow signal mirrors
# the rollback/preemption surface above — raised at step fences when the
# supervisor announced a new mesh generation, handled by the generation
# loops (train.gpt, Trainer.fit).
from tpuflow.dist.membership import MeshReform  # noqa: F401  (re-export)
from tpuflow.utils import knobs


def dispatch_depth(default: int = 2) -> int:
    """Resolve the dispatch-ahead window depth (ISSUE 4).

    ``TPUFLOW_DISPATCH_DEPTH`` steps may be in flight on the accelerator
    before the host materializes the oldest step's scalars (loss, health
    numerics): the hot loops push each step's outputs into a
    :class:`DispatchWindow` and only settle — ``float()`` the device
    scalars, which is the true fence — once the window is full. Depth 1
    reproduces the old settle-every-step loop exactly; the default of 2
    keeps one step queued behind the executing one, so host-side work
    (batch placement, telemetry, the health monitor) overlaps device
    compute instead of serializing with it.

    Values < 1 clamp to 1; a malformed value falls back to ``default``
    (the loop must never die on a typo'd env var mid-provisioning).

    Platform note: on the serialized host-CPU dev platform the loops
    still ``dist.step_fence`` each step at dispatch (XLA:CPU's
    collective rendezvous kills the process when more than one
    collective program is in flight on a starved host — see
    ``dist.serialize_steps``), so there the window only defers the
    host-side accounting; on accelerators the window IS the only
    per-step synchronization.
    """
    env = knobs.raw("TPUFLOW_DISPATCH_DEPTH")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return max(1, default)


class DispatchWindow:
    """Bounded dispatch-ahead bookkeeping for a fenced step loop.

    Pure host-side bookkeeping (no jax dependency): the loop ``push``es
    one opaque entry per dispatched step; once ``depth`` entries are
    pending, ``push`` returns the oldest entries (the matured ones) for
    the caller to settle — the caller's settle function does the actual
    fence (``float()`` on a device scalar blocks until that step's
    program finished, which transitively bounds the in-flight window).
    ``drain()`` matures everything pending (epoch end, preemption drain,
    pre-checkpoint barrier); ``clear()`` abandons pending entries
    without settling them (divergence rollback: the in-flight steps are
    being discarded along with the state they produced).

    Why the caller settles instead of a callback: settle raises —
    health anomalies unwind the epoch loop via ``_RollbackSignal`` — and
    the raise must happen in the loop's own try block, not inside a
    helper frame holding half-consumed state.
    """

    def __init__(self, depth: int = 1):
        self.depth = max(1, int(depth))
        self._pending: collections.deque = collections.deque()

    def push(self, entry) -> list:
        """Queue one dispatched step; return entries due for settling
        (oldest first). With depth N, the entry pushed for step i
        matures when step i+N-1 is pushed — depth 1 returns every entry
        immediately (the settle-every-step loop)."""
        self._pending.append(entry)
        out = []
        while len(self._pending) >= self.depth:
            out.append(self._pending.popleft())
        return out

    def drain(self) -> list:
        """Mature every pending entry (oldest first)."""
        out = list(self._pending)
        self._pending.clear()
        return out

    def clear(self) -> None:
        """Abandon pending entries WITHOUT settling (rollback path)."""
        self._pending.clear()

    def __len__(self) -> int:
        return len(self._pending)


class StepClock:
    """Per-step wall-time telemetry for a fenced step loop.

    The epoch loops (tpuflow.train.gpt) fence every step (dist.step_fence),
    so host-side monotonic deltas between fences ARE per-step wall time —
    this clock turns them into the unified telemetry stream: a
    ``train.compile`` span for the cold first step (jit trace + compile +
    first execution, the part that must be split out or it poisons every
    throughput number), a ``train.step_s`` histogram observation per
    steady-state step, and a ``train.tokens`` counter for tokens/sec
    derivation. Every method is a no-op when telemetry is disabled — the
    loop pays one attribute check per step, nothing else (pinned by
    tests/test_obs.py overhead guard).
    """

    def __init__(self):
        self._on = obs.enabled()
        # Anomaly-triggered profiler capture (ISSUE 15): None unless
        # TPUFLOW_PROF_TRIGGER — the disarmed hot path is one
        # `is not None` check per fenced step (pinned by the
        # tests/test_obs.py overhead guard).
        self._cap = _profcap.maybe_from_env()
        track = self._on or self._cap is not None
        self._last = time.monotonic() if track else 0.0
        self._t0 = self._last
        self._ts0 = time.time() if self._on else 0.0
        self._steps = 0
        if self._on:
            # One clock per train leg: restart the live goodput ledger
            # (tpuflow.obs.goodput) the export endpoint serves, so
            # /metrics reflects THIS leg, not a previous run in the same
            # process.
            _goodput.live().reset()

    def reset(self) -> None:
        """Restart the clock (epoch boundary / after the compile fence)."""
        if self._on or self._cap is not None:
            self._last = time.monotonic()

    def compile_done(self, **attrs) -> float | None:
        """The cold first step just fenced: record it as train.compile.
        Returns the compile wall seconds when recording (the train legs
        hand it to the device ledger's compile-fence entry), else None."""
        _heartbeat()
        if not (self._on or self._cap is not None):
            return None
        now = time.monotonic()
        dur = now - self._t0
        self._last = now
        if not self._on:
            return None
        rec = obs.recorder()
        if rec is not None:
            rec.record(
                "span", "train.compile", ts=self._ts0,
                dur_s=dur, **attrs,
            )
        _goodput.live().note_compile(dur)
        _goodput.emit_gauges()
        # First post-compile HBM reading (ISSUE 15): the compiled
        # programs' buffers just landed — the most informative poll of
        # the run (self-disabling off-TPU).
        _device.maybe_emit_hbm(force=True)
        return dur

    def step_done(self, tokens: int = 0, step: int | None = None) -> None:
        """A steady-state step just fenced: record its wall time. Also
        stamps this gang member's heartbeat — the step fence is the
        liveness signal the gang supervisor watches (no-op outside a
        supervised gang), now carrying the CURRENT step number so a stall
        report can say where the member stopped."""
        _heartbeat(step)
        cap = self._cap
        if not self._on:
            if cap is not None:
                now = time.monotonic()
                cap.observe_step(now - self._last, step)
                self._last = now
            return
        now = time.monotonic()
        dur = now - self._last
        obs.histogram("train.step_s", dur)
        if tokens:
            obs.counter("train.tokens", tokens)
        self._last = now
        _goodput.live().note_step(dur, tokens=tokens, step=step)
        if cap is not None:
            # Median+MAD step-time spike detector (ISSUE 15); the same
            # call advances a live capture's bound.
            cap.observe_step(dur, step)
        self._steps += 1
        if self._steps % 32 == 0:
            # Periodic goodput-so-far gauges: cheap (three buffered
            # records), and the event stream then carries the
            # incremental ledger even for runs that die mid-epoch.
            _goodput.emit_gauges()
            # HBM gauges ride the same cadence, throttled further by
            # TPUFLOW_DEVICE_POLL_S (one bool check off-TPU).
            _device.maybe_emit_hbm()

    def goodput_mark(self) -> None:
        """Epoch-fence hook: flush the goodput-so-far gauges so every
        epoch boundary has a fresh incremental ledger reading."""
        if self._on:
            _goodput.emit_gauges()
            _device.maybe_emit_hbm()

    @property
    def recording(self) -> bool:
        """Whether this clock's run records telemetry — the loops use it
        to decide, once per step, whether to host-copy the numerics
        scalars for the ``health.*`` gauges."""
        return self._on

    def health_done(
        self,
        *,
        loss: float,
        grad_norm: float,
        update_norm: float,
        param_norm: float,
        nonfinite: bool,
    ) -> None:
        """Record the fenced step's on-device numerics (ISSUE 3). The
        scalars were computed inside the jitted step and materialized by
        the fence the loop already paid — this only copies four floats
        into the event buffer. No-op when telemetry is disabled."""
        if nonfinite and self._cap is not None:
            # Direct capture trigger (ISSUE 15): the numerics went bad;
            # the trace shows what the device was doing when they did.
            self._cap.note_nonfinite()
        if not self._on:
            return
        obs.gauge("health.loss", loss)
        obs.gauge("health.grad_norm", grad_norm)
        obs.gauge("health.update_norm", update_norm)
        obs.gauge("health.param_norm", param_norm)
        if nonfinite:
            obs.counter("health.nonfinite")
        _goodput.live().note_health(loss, grad_norm, nonfinite)


class TrainState(train_state.TrainState):
    """Flax TrainState: {step, params, opt_state} pytree + static apply_fn/tx.

    The pytree leaves are exactly the checkpoint payload of the reference
    ({epoch, model_state_dict, optimizer_state_dict}, my_ray_module.py:183-185)
    plus the step counter. ``batch_stats`` carries BatchNorm running
    statistics for models that have them (ResNets); it is an empty dict
    otherwise. Under pjit/GSPMD the batch-mean reduction is over the GLOBAL
    (logically unsharded) batch, so the running statistics are identical on
    every replica by construction — stronger than torch DDP's per-replica
    stats (reference my_ray_module.py:135), where replicas silently diverge.
    The checkpoint therefore stores the one true global statistic
    (pinned by tests/test_train_step.py::test_batchnorm_stats_are_global).
    """

    batch_stats: Any = flax.struct.field(default_factory=dict)
    # Exponential moving average of params (empty dict = EMA off). Enable
    # with ``with_ema(state)`` + ``make_train_step(ema_decay=...)``; the
    # averaged weights ride the state pytree, so they checkpoint/restore
    # with everything else and evaluate via ``state.replace(params=
    # state.ema_params)``.
    ema_params: Any = flax.struct.field(default_factory=dict)


def create_train_state(model, rng, sample_input, tx) -> TrainState:
    """Initialize params and optimizer state (reference my_ray_module.py:131,
    141-142: NeuralNetwork() + SGD(lr, momentum=0.9))."""
    variables = model.init(rng, sample_input, train=False)
    return TrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        tx=tx,
    )


def _variables(state: TrainState, params):
    v = {"params": params}
    if state.batch_stats:
        v["batch_stats"] = state.batch_stats
    return v


def per_worker_batch_size(global_batch_size: int, num_workers: int) -> int:
    """Per-shard batch = global // num_workers, floor division exactly as the
    reference computes it (my_ray_module.py:230)."""
    per = global_batch_size // num_workers
    if per < 1:
        raise ValueError(
            f"global batch {global_batch_size} too small for {num_workers} workers"
        )
    return per


def with_ema(state: TrainState) -> TrainState:
    """Seed EMA tracking: the averaged weights start as a COPY of the
    current params (distinct buffers — aliasing them would donate the same
    buffer through two pytree leaves on the first donated step). Pair with
    ``make_train_step(ema_decay=...)``."""
    return state.replace(
        ema_params=jax.tree_util.tree_map(jnp.copy, state.params)
    )


def comm_overlap_enabled(default: bool = True) -> bool:
    """Resolve the comm/compute-overlap knob (ISSUE 10).

    ``TPUFLOW_COMM_OVERLAP=0`` disables the per-microbatch gradient
    reduce-scatter inside the accumulation scan (and the async-collective
    XLA flags ``dist.maybe_enable_async_collectives`` would stage);
    anything else — including unset — leaves it on. The knob only
    changes programs where it can matter: ``make_train_step`` applies it
    when ``accum_steps > 1`` AND the caller passed ``grad_shardings``.
    """
    return knobs.raw("TPUFLOW_COMM_OVERLAP", "1").lower() not in (
        "0", "false", "off",
    )


def make_train_step(
    loss_fn: Callable = cross_entropy_loss,
    *,
    donate: bool = True,
    accum_steps: int = 1,
    ema_decay: float | None = None,
    grad_shardings: Any = None,
    comm_overlap: bool | None = None,
) -> Callable:
    """Build the jitted SPMD train step.

    The returned ``fn(state, batch, rng) -> (state, metrics)`` is traced once
    and compiled by XLA (static shapes; the Python epoch loop only feeds
    sharded batches, SURVEY.md §3.5). ``rng`` is folded with ``state.step`` so
    dropout masks differ per step while the traced function stays pure.

    ``accum_steps > 1`` enables gradient accumulation: the batch's leading
    axis splits into that many equal microbatches, a ``lax.scan`` runs
    forward+backward per microbatch (peak activation memory drops by the
    same factor), averaged gradients feed ONE optimizer update — numerically
    identical to the full-batch step for mean losses (pinned by
    tests/test_train_step.py). The scan is a compiler-friendly loop: one
    trace, static shapes, grads carried in place.

    Comm/compute overlap (ISSUE 10): with ``grad_shardings`` (the
    per-leaf param shardings of the FSDP leg) and ``accum_steps > 1``,
    each microbatch's gradient is pinned to those shardings INSIDE the
    scan body (``with_sharding_constraint``), which makes GSPMD emit the
    gradient reduce-scatter per microbatch — right behind that
    microbatch's backward — instead of one deferred reduction after the
    whole scan. With the async-collective XLA flags staged
    (``dist.maybe_enable_async_collectives``), the TPU scheduler then
    hides each bucket's DCN/ICI time behind the NEXT microbatch's
    backward compute; the accumulator also stays SHARDED, cutting its
    HBM footprint by the fsdp world size. The bucketing is the gradient
    tree itself: each leaf is one collective, issued the moment its
    microbatch produces it. ``comm_overlap=None`` resolves
    ``TPUFLOW_COMM_OVERLAP`` (default on); the sequential scan is
    recovered with ``TPUFLOW_COMM_OVERLAP=0``, and tests pin the two
    programs' losses against each other
    (tests/test_train_step.py::test_comm_overlap_scan_matches_sequential).

    Donation audit (ISSUE 4, dispatch-ahead): argument 0 (the state) is
    donated — XLA reuses its buffers for the new state, so the OLD state
    must never be touched after the call. The hot loops honor this by
    (a) rebinding ``state`` before the next dispatch and (b) keeping
    only the step's *outputs* (the metrics dict) alive in the
    :class:`DispatchWindow` while up to ``dispatch_depth()`` steps are
    in flight; batches and rng are NOT donated, so the prefetch thread's
    placed batches stay valid however late the step executes (pinned by
    tests/test_train_step.py donation-safety tests).
    """

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if ema_decay is not None and not 0.0 < ema_decay < 1.0:
        raise ValueError(
            f"ema_decay must be in (0, 1), got {ema_decay} (>= 1 freezes or "
            "diverges the average)"
        )
    if comm_overlap is None:
        comm_overlap = comm_overlap_enabled()
    overlap_active = (
        comm_overlap and grad_shardings is not None and accum_steps > 1
    )

    def _pin_grads(tree):
        # One with_sharding_constraint per gradient leaf: the per-
        # microbatch reduce-scatter "bucket" issue points (overlap path).
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, grad_shardings
        )

    def train_step(state: TrainState, batch, rng):
        base_rng = jax.random.fold_in(rng, state.step)
        has_stats = bool(state.batch_stats)

        def compute_loss(params, batch_stats, mb, dropout_rng):
            # 'losses' collects auxiliary objectives the model sows (e.g. the
            # MoE load-balance loss); models without any sow leave it empty.
            mutable = ["losses"] + (["batch_stats"] if has_stats else [])
            variables = {"params": params}
            if has_stats:
                variables["batch_stats"] = batch_stats
            logits, updates = state.apply_fn(
                variables,
                mb["x"],
                train=True,
                rngs={"dropout": dropout_rng},
                mutable=mutable,
            )
            from tpuflow.models.losses import sum_sown_losses

            with jax.named_scope("loss"):
                loss = loss_fn(logits, mb["y"]) + sum_sown_losses(updates)
            return loss, (logits, updates)

        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

        if accum_steps == 1:
            (loss, (logits, updates)), grads = grad_fn(
                state.params, state.batch_stats, batch, base_rng
            )
            acc = accuracy(logits, batch["y"])
            new_stats = updates.get("batch_stats") if has_stats else None
        else:
            n_rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if n_rows % accum_steps:
                raise ValueError(
                    f"batch of {n_rows} rows does not split into "
                    f"accum_steps={accum_steps} equal microbatches"
                )
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(
                    accum_steps, x.shape[0] // accum_steps, *x.shape[1:]
                ),
                batch,
            )

            def body(carry, inp):
                gsum, lsum, asum, stats = carry
                mb, idx = inp
                (l, (logits, updates)), g = grad_fn(
                    state.params, stats, mb, jax.random.fold_in(base_rng, idx)
                )
                if overlap_active:
                    # Pin THIS microbatch's gradients to the param
                    # shardings: GSPMD reduce-scatters them here, inside
                    # the scan body, where the async scheduler can slide
                    # the collective behind the next microbatch's
                    # backward — instead of one exposed reduction after
                    # the scan. The carried sum is then sharded too.
                    g = _pin_grads(g)
                carry = (
                    jax.tree_util.tree_map(jnp.add, gsum, g),
                    lsum + l,
                    asum + accuracy(logits, mb["y"]),
                    updates["batch_stats"] if has_stats else stats,
                )
                return carry, None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            if overlap_active:
                zeros = _pin_grads(zeros)
            (gsum, lsum, asum, new_stats), _ = jax.lax.scan(
                body,
                (zeros, 0.0, 0.0, state.batch_stats),
                (micro, jnp.arange(accum_steps)),
            )
            # Equal microbatches: the mean of microbatch means IS the
            # full-batch mean, for the loss and its gradient alike.
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / accum_steps).astype(p.dtype),
                gsum,
                state.params,
            )
            loss = lsum / accum_steps
            acc = asum / accum_steps
        import optax

        # Explicit tx.update (what TrainState.apply_gradients wraps): the
        # produced ``updates`` tree feeds the health telemetry below
        # without a second optimizer pass or a params diff.
        with jax.named_scope("optimizer"):
            updates, new_opt_state = state.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )
        if has_stats:
            new_state = new_state.replace(batch_stats=new_stats)
        if ema_decay is not None:
            if not state.ema_params:
                raise ValueError(
                    "ema_decay is set but the state carries no ema_params; "
                    "seed them with tpuflow.train.with_ema(state)"
                )
            new_state = new_state.replace(
                ema_params=jax.tree_util.tree_map(
                    lambda e, p: e * ema_decay + (1.0 - ema_decay) * p,
                    state.ema_params,
                    new_state.params,
                )
            )
        # Pre-clip global gradient norm plus the rest of the on-device
        # numerics telemetry (update/param norms, fused NaN/Inf flag) —
        # tiny fused reductions, noise next to the backward pass; the
        # HealthMonitor and the health.* gauges read these post-fence.
        # Under the update's scope: XLA fuses the update into these
        # reductions over the same arrays, and a fusion carries one name.
        from tpuflow.train.optim import health_stats

        with jax.named_scope("optimizer"):
            health = health_stats(loss, grads, updates, new_params)
        metrics = {"loss": loss, "accuracy": acc, **health}
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def run_validation(state, loader, eval_step, *, place=None) -> float:
    """Mean per-element loss over a (possibly pad_tail) eval loader.

    The loader's per-row mask is broadcast to the label shape (so LM
    batches mask whole padded rows of tokens), every batch runs through the
    jitted ``eval_step``, and the masked sums accumulate host-side.
    ``place`` maps host arrays onto devices (default ``jnp.asarray``; pass
    a sharded ``device_put`` for mesh execution). One implementation shared
    by the training flows' per-epoch validation and the eval flows."""
    import numpy as np

    if place is None:
        place = jnp.asarray
    tot = cnt = 0.0
    for b in loader:
        batch = {"x": place(b["x"]), "y": place(b["y"])}
        mask = b.get("mask")
        if mask is not None:
            if mask.shape != b["y"].shape:
                mask = np.broadcast_to(mask[:, None], b["y"].shape)
            batch["mask"] = place(np.ascontiguousarray(mask, np.float32))
        m = eval_step(state, batch)
        tot += float(m["loss_sum"])
        cnt += float(m["count"])
    return tot / max(cnt, 1.0)


def make_eval_step(loss_fn: Callable = cross_entropy_loss) -> Callable:
    """Build the jitted eval step for the full validation pass
    (reference my_ray_module.py:162-175).

    Returns per-batch ``{loss_sum, num_correct, count}`` so the caller can
    accumulate across fixed-shape batches, honoring a ``mask`` entry (1 for
    real rows, 0 for tail padding — SURVEY.md §7 hard-part 5: XLA needs
    static shapes, so ragged tails are padded and masked out).
    """

    def eval_step(state: TrainState, batch):
        logits = state.apply_fn(
            _variables(state, state.params), batch["x"], train=False
        )
        labels = batch["y"]
        per_row = -jnp.take_along_axis(
            jax.nn.log_softmax(logits), labels[..., None], axis=-1
        )[..., 0]
        correct = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(labels.shape, jnp.float32)
        return {
            "loss_sum": jnp.sum(per_row * mask),
            "num_correct": jnp.sum(correct * mask),
            "count": jnp.sum(mask),
        }

    return jax.jit(eval_step)


# ---------------------------------------------- comm/compute attribution
# Aggregate ICI bandwidth per chip (GB/s, approximate public figures),
# matched against jax.devices()[0].device_kind like the goodput ledger's
# bf16-peak table. The denominator of the comm roofline below — an
# ATTRIBUTION model, not a measurement, so round numbers are fine.
_ICI_GBPS = (
    ("v6 lite", 800.0),
    ("v6lite", 800.0),
    ("v6e", 800.0),
    ("v5 lite", 400.0),
    ("v5lite", 400.0),
    ("v5e", 400.0),
    ("v5p", 1200.0),
    ("v5", 1200.0),
    ("v4", 300.0),
)


def _ici_gbps() -> float | None:
    from tpuflow.obs import goodput as _gp

    return _gp.device_table_value(_ICI_GBPS, "ICI bandwidth")


def comm_attribution(
    step_s: float,
    *,
    tokens: int,
    n_params: int,
    accum_steps: int = 1,
    fsdp_world: int = 1,
    overlapped: bool = True,
) -> dict | None:
    """Roofline attribution of one step's wall time into compute vs
    exposed communication (ISSUE 10): the numbers behind the
    ``train.exposed_comm_s`` / ``train.comm_overlap_s`` gauges and the
    bench train leg's ``exposed_comm_s`` record.

    This is a MODEL, stated as bounds, not a device measurement (XLA
    fuses the collectives into the step program; the host cannot time
    them separately without a profiler capture):

    - ``ideal_compute_s`` = 6·N FLOPs/token × tokens ÷ (bf16 peak ×
      devices) — the same estimate the rolling-MFU gauge uses.
    - ``exposed_comm_s`` = max(0, step_s − ideal_compute_s): every
      second the step spent NOT at peak compute. An UPPER bound on
      exposed communication (memory stalls and pipeline bubbles charge
      here too — attributing them to comm keeps the overlap claim
      conservative).
    - ``ideal_comm_s``: the FSDP step's collective volume at aggregate
      ICI bandwidth — per microbatch a param all-gather for fwd and one
      for the (remat) bwd, plus a gradient reduce-scatter per microbatch
      when overlapped (once per step when sequential: overlap trades
      (accum−1) extra grad reductions for hideability), each moving
      4 bytes × N × (w−1)/w per device.
    - ``comm_overlap_s`` = max(0, ideal_comm_s − exposed_comm_s): a
      LOWER bound on the comm time hidden behind compute.

    Returns None off-TPU (no peak table — an invented attribution would
    be noise) and with ``fsdp_world <= 1`` sets the comm terms to 0
    (single-shard: nothing to gather or scatter).
    """
    from tpuflow.obs import goodput as _gp

    peak = _gp._peak_flops_per_device()
    if peak is None or step_s <= 0.0 or n_params <= 0:
        return None
    import jax

    ndev = max(jax.device_count(), 1)
    ideal_compute_s = 6.0 * n_params * tokens / (peak * ndev)
    exposed = max(0.0, step_s - ideal_compute_s)
    ideal_comm_s = 0.0
    ici = _ici_gbps()
    if fsdp_world > 1 and ici:
        frac = (fsdp_world - 1) / fsdp_world
        bytes_per_pass = 4.0 * n_params * frac
        ag_passes = 2 * max(accum_steps, 1)
        rs_passes = max(accum_steps, 1) if overlapped else 1
        ideal_comm_s = (ag_passes + rs_passes) * bytes_per_pass / (
            ici * 1e9
        )
    return {
        "ideal_compute_s": ideal_compute_s,
        "ideal_comm_s": ideal_comm_s,
        "exposed_comm_s": exposed,
        "comm_overlap_s": max(0.0, ideal_comm_s - exposed),
        "overlapped": bool(overlapped),
    }


def emit_comm_gauges(att: dict | None) -> None:
    """Publish a step's comm attribution onto the telemetry stream.
    No-op when the attribution is unavailable (off-TPU) or telemetry is
    disabled — the gauges only ever carry chip-grounded values."""
    if att is None or not obs.enabled():
        return
    obs.gauge("train.exposed_comm_s", round(att["exposed_comm_s"], 6))
    obs.gauge("train.comm_overlap_s", round(att["comm_overlap_s"], 6))
