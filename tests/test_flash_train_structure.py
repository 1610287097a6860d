"""The train step at 1,024 positions never holds a score matrix (ISSUE 31).

Read off the jaxpr of the scanned, rematted GPT-2 train step (no chip, and
nothing runs: the step is traced from shapes), at the ``test`` width and
the sequence length the benchmark's training cell runs:

- with the flash implementation no array of the step has two trailing
  dimensions of T x T — the (B, H, T, T) scores and probabilities that
  XLA's attention writes to HBM three times a layer; the control traces
  the same step with XLA attention and finds them, so the check can fail;
- attention is two kernels a layer (the forward and ONE backward; none
  under the block's remat, ISSUE 38), each under the ``attn_core``
  scope that ``attn_core_share.train`` and ``attn_core_roofline.train``
  read device time by, and each on whole 1,024-position rows, so no
  transposed copy of q, k, v or a gradient is made around them;
- a rematerialised block keeps its input and the kernel's o and lse, each
  once and o as ``(L, B, T, C)`` rows (ISSUE 38): the forward scan stacks
  those three and nothing else, so the recompute holds no kernel; under
  XLA attention nothing carries the names and the step is the program of
  ``remat_policy="nothing_saveable"``, equation for equation;
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


def _traced_step(impl: str, remat_policy: str | None = None):
    cfg = GPT2Config.small_test(
        n_ctx=T, dropout=0.0, scan_layers=True, remat=True, attn_impl=impl,
        dtype=jnp.bfloat16, remat_policy=remat_policy,
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
    # The forward and one backward; the block's remat re-runs neither.
    assert len(stacks) == 2, stacks
    assert all("attn_core" in s.split("/") for s in stacks), stacks
    assert sum("rematted_computation" in s for s in stacks) == 0, stacks


def _forward_scan_stacks(eqns, n_layer):
    """Shapes of what the step's forward layer scan stacks for the
    backward: its scanned outputs (the scan that runs the forward kernel
    and not the backward one)."""

    def kernels(eqn):
        return [
            len(e.outvars) for e in _eqns(eqn.params["jaxpr"].jaxpr)
            if e.primitive.name == "pallas_call"
        ]

    forward = [
        eqn for eqn in eqns
        if eqn.primitive.name == "scan" and eqn.params["length"] == n_layer
        and kernels(eqn) == [2]  # o and lse; the backward writes dq, dk, dv
    ]
    assert len(forward) == 1, forward
    (scan,) = forward
    return sorted(v.aval.shape for v in scan.outvars[scan.params["num_carry"]:])


def test_rematted_block_keeps_o_and_lse_once(flash_step):
    cfg, eqns, _ = flash_step
    L, H = cfg.n_layer, cfg.n_head
    rows = (L, B, T, cfg.n_embd)  # x, and o as the kernel writes it
    stacks = _forward_scan_stacks(eqns, L)
    lse = [s for s in stacks if s != rows]
    assert stacks.count(rows) == 2 and len(stacks) == 3, stacks
    # lse: one float32 row a head, grouped as the kernel writes it.
    assert len(lse[0]) == 5 and lse[0][:2] == (L, B), lse
    assert lse[0][2] * lse[0][3] == H and lse[0][4] == T, lse
    # Never o a second time as (L, B, T, H, D), which the chip pads.
    assert (L, B, T, H, cfg.n_embd // H) not in stacks


def _named(eqns):
    return [eqn.params["name"] for eqn in eqns if eqn.primitive.name == "name"]


def test_flash_step_names_o_and_lse(flash_step):
    _, eqns, _ = flash_step
    assert sorted(set(_named(eqns))) == ["flash_lse", "flash_out"]


def test_xla_step_is_the_parents_program():
    """No value of an XLA-attention step carries a name, so the default
    policy keeps a block's input alone: the same equations as under
    ``nothing_saveable``, which is what policy-less remat was."""
    _, kept = _traced_step("xla")
    _, bare = _traced_step("xla", "nothing_saveable")
    assert _named(kept) == []

    def outline(eqns):
        return [
            (eqn.primitive.name, tuple(v.aval.str_short() for v in eqn.outvars))
            for eqn in eqns
        ]

    assert outline(kept) == outline(bare)


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
