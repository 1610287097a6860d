"""The train step at 1,024 positions never holds a score matrix (ISSUE 31).

Read off the jaxpr of the scanned, rematted GPT-2 train step (no chip, and
nothing runs: the step is traced from shapes), at the ``test`` width and
the sequence length the benchmark's training cell runs:

- with the flash implementation no array of the step has two trailing
  dimensions of T x T — the (B, H, T, T) scores and probabilities that
  XLA's attention writes to HBM three times a layer; the control traces
  the same step with XLA attention and finds them, so the check can fail;
- attention is three kernels a layer (the forward, the forward recomputed
  under the block's remat, ONE backward), each under the ``attn_core``
  scope that ``attn_core_share.train`` and ``attn_core_roofline.train``
  read device time by, and each on whole 1,024-position rows, so no
  transposed copy of q, k, v or a gradient is made around them;
- the trace announces the fused backward with the blocks the shape chose.
"""

import jax
import jax.numpy as jnp
import optax
import pytest

from tpuflow.models.gpt2 import GPT2, GPT2Config
from tpuflow.train import TrainState, make_train_step

B, T = 2, 1024


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, remat, jit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _traced_step(impl: str):
    cfg = GPT2Config.small_test(
        n_ctx=T, dropout=0.0, scan_layers=True, remat=True, attn_impl=impl,
        dtype=jnp.bfloat16,
    )
    model = GPT2(cfg)

    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        )

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    jaxpr = jax.make_jaxpr(make_train_step(donate=False))(
        state, {"x": tokens, "y": tokens}, jax.random.PRNGKey(1)
    )
    return cfg, list(_eqns(jaxpr.jaxpr))


@pytest.fixture(scope="module")
def flash_step():
    events = []
    from tpuflow import obs

    real = obs.event
    obs.event = lambda name, **attrs: events.append((name, attrs))
    try:
        cfg, eqns = _traced_step("flash")
    finally:
        obs.event = real
    return cfg, eqns, events


def _score_shaped(eqns):
    return [
        (eqn.primitive.name, v.aval.shape)
        for eqn in eqns
        for v in eqn.outvars
        if getattr(v.aval, "shape", ())[-2:] == (T, T)
    ]


def test_flash_step_holds_no_score_matrix(flash_step):
    _, eqns, _ = flash_step
    assert _score_shaped(eqns) == []


def test_xla_step_holds_the_score_matrix():
    cfg, eqns = _traced_step("xla")
    shapes = {shape for _, shape in _score_shaped(eqns)}
    assert (B, cfg.n_head, T, T) in shapes


def test_every_kernel_lies_under_attn_core(flash_step):
    _, eqns, _ = flash_step
    stacks = [
        str(eqn.source_info.name_stack)
        for eqn in eqns if eqn.primitive.name == "pallas_call"
    ]
    # Forward, the forward recomputed under remat, one backward.
    assert len(stacks) == 3, stacks
    assert all("attn_core" in s.split("/") for s in stacks), stacks
    assert sum("rematted_computation" in s for s in stacks) == 1, stacks


def test_kernels_take_whole_rows_in_the_models_layout(flash_step):
    cfg, eqns, _ = flash_step
    wide = (B, T, cfg.n_embd)  # (B, T, H·D): q, k, v, o and the gradients
    calls = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"]
    for eqn in calls:
        big = [
            v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
            if len(v.aval.shape) == 3
        ]
        assert big and all(shape == wide for shape in big), big
    backward = [eqn for eqn in calls if len(eqn.outvars) == 3]
    assert len(backward) == 1  # dq, dk and dv of one recompute of p
    # Nothing is transposed on its way into or out of a kernel.
    moved = [
        eqn for eqn in eqns
        if eqn.primitive.name == "transpose"
        and eqn.outvars[0].aval.shape
        == (B, cfg.n_head, T, cfg.n_embd // cfg.n_head)
    ]
    assert moved == []


def test_trace_announces_the_fused_backward(flash_step):
    cfg, _, events = flash_step
    fused = [attrs for name, attrs in events if name == "ops.flash_bwd_fused"]
    assert fused == [
        dict(seq=T, heads=cfg.n_head, causal=True, block_q=T, block_k=T)
    ]
