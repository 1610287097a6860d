"""Attention implementations with one call signature, selectable per model.

``attention(q, k, v, *, causal, impl)`` with q/k/v shaped (B, T, H, D).
Implementations:

- ``"xla"``   — plain einsum softmax attention; XLA fuses it well for short
  sequences and it runs on any backend (the CPU test mesh included).
- ``"flash"`` — Pallas TPU blockwise (flash) attention kernel, O(T) memory
  (tpuflow.ops.flash_attention).
- ``"ring"``  — ring attention over the 'seq' mesh axis for long-context
  sequence parallelism (tpuflow.parallel.ring_attention): KV blocks rotate
  around the ring via collective-permute while each shard computes blockwise
  attention — the TPU-native long-context strategy (absent from the reference,
  which has no attention at all; SURVEY.md §5 long-context).
- ``"ulysses"`` — all-to-all sequence parallelism over 'seq'
  (tpuflow.parallel.ulysses): all_to_alls swap q/k/v to full-sequence /
  head-sharded layout, attention runs locally with plain causal masking,
  one more all_to_all swaps the output back — 4 collectives per call vs
  ring's s-step rotation.

The reference has no attention op anywhere (its model is an image MLP,
my_ray_module.py:94-112); these exist for the GPT-2 acceptance config and the
framework's first-class long-context support.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from tpuflow.utils import knobs


def xla_attention(q, k, v, *, causal: bool = True):
    """Reference einsum attention. q,k,v: (B, T, H, D) → (B, T, H, D).

    Softmax statistics in float32 regardless of input dtype (bf16-safe on the
    MXU: the matmuls stay bf16, the normalization doesn't lose precision).
    """
    B, T, H, D = q.shape
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# Shipped defaults for the auto-dispatch thresholds, from chip calls 93 and
# 95 of PR 31 (PERF.md §6; TPU v5e, bf16, causal, heads of 64, the kernels
# against `xla_attention` in one process). The crossover is not a length:
# XLA's attention is fast while the (B, H, T, T) scores stay on the chip
# and slow once they go through HBM, and the kernels' time follows B·H·T.
# Up to 21M score elements XLA is level or ahead (forward, device ms a
# call, XLA against the kernels: B·H = 20 at T=1024 0.071 against 0.071,
# B·H = 80 at T=512 0.072 against 0.115, at T=256 0.017 against 0.054;
# forward + backward at B·H = 80, T=256 0.041 against 0.110); from 42M the
# kernels are ahead (forward / forward + backward at 8,192 tokens of 20
# heads: T=256 0.61 / 1.49 against 0.51 / 1.22, T=512 1.09 / 3.44 against
# 0.55 / 1.30, T=1024 2.05 / 6.64 against 0.63 / 1.59, T=2048 4.09 / 12.6
# against 1.04 / 2.67; B·H = 48 at T=1024 0.59 against 0.16 forward). A
# threshold on T cannot say that, so both defaults are the shortest row at
# which no measured shape lost: 1,024 (level at one row of 12 to 20 heads,
# 3.2 to 4.2 times ahead from four). They stay two names because the
# tuning file and the two environment variables set them apart.
_DEFAULT_FLASH_MIN_SEQ = 1024
_DEFAULT_FLASH_MIN_SEQ_FWD = 1024
_flash_tuning_cache: dict | None = None
_warned_malformed_env = False
_warned_malformed_tuning = False


def flash_tuning_path() -> str:
    """Where ``bench.py`` persists the measured flash/XLA crossovers on
    this host: ``$TPUFLOW_HOME/flash_tuning.json`` with
    ``{"flash_min_seq": T_fwdbwd, "flash_min_seq_fwd": T_fwd,
    "flash_min_seq_bwd": T_bwdonly}``."""
    import os

    home = knobs.raw(
        "TPUFLOW_HOME", os.path.join(os.path.expanduser("~"), ".tpuflow")
    )
    return os.path.join(home, "flash_tuning.json")


def _flash_tuning() -> dict:
    import json

    global _flash_tuning_cache
    if _flash_tuning_cache is None:
        try:
            with open(flash_tuning_path()) as f:
                _flash_tuning_cache = json.load(f)
        except (OSError, ValueError):
            _flash_tuning_cache = {}
    return _flash_tuning_cache


def _flash_min_seq(*, needs_bwd: bool = True) -> int:
    """Dispatch threshold resolution, independently for the fwd+bwd
    (training) and fwd-only (inference) paths: the env var
    (TPUFLOW_FLASH_MIN_SEQ / TPUFLOW_FLASH_MIN_SEQ_FWD) beats the host's
    measured tuning file beats the shipped default. A MALFORMED env var
    falls through to the tuning-file lookup (the host's measured
    crossover — strictly better information than the shipped constant)
    and warns once per process, through the obs stream when one is live.
    An unset fwd-only env var falls back to only its own sources; the
    two paths never borrow each other's thresholds. The file read is
    cached per process (this runs at trace time).

    The training path additionally consults the fitted BWD-ONLY
    crossover (ISSUE 10 satellite; bench's T512/T2048 ``jax.vjp`` timing
    split, persisted as ``flash_min_seq_bwd``): the effective fwd+bwd
    threshold is the max of the valid measured entries — below the
    measured backward-kernel crossover the bwd kernels are a MEASURED
    loss, so fwd+bwd dispatch must pick XLA there even when the fwd+bwd
    composition point is absent or was discarded as timing-suspect. A
    malformed tuning entry (present but not a positive integer) is
    ignored with a once-per-process warning; no valid entry at all falls
    back to the shipped default."""
    import os

    global _warned_malformed_env
    env_name = (
        "TPUFLOW_FLASH_MIN_SEQ" if needs_bwd else "TPUFLOW_FLASH_MIN_SEQ_FWD"
    )
    # tpulint: disable=knob-dynamic -- env_name is one of two literal
    # TPUFLOW_FLASH_MIN_SEQ* names selected two lines up; both are
    # declared and the string-literal rule validates them.
    env = knobs.raw(env_name)
    if env is not None:
        try:
            return int(env)
        except ValueError:
            if not _warned_malformed_env:
                _warned_malformed_env = True
                import warnings

                from tpuflow import obs

                warnings.warn(
                    f"{env_name}={env!r} is not an integer; "
                    "falling through to the tuning file / default",
                    stacklevel=2,
                )
                obs.event("warn.flash_min_seq_malformed", value=env)
            # fall through to the measured tuning file below
    keys = (
        ("flash_min_seq", "flash_min_seq_bwd")
        if needs_bwd
        else ("flash_min_seq_fwd",)
    )
    fitted = [_tuning_entry(k) for k in keys]
    fitted = [v for v in fitted if v is not None]
    if fitted:
        return max(fitted)
    return (
        _DEFAULT_FLASH_MIN_SEQ if needs_bwd else _DEFAULT_FLASH_MIN_SEQ_FWD
    )


def _tuning_entry(key: str) -> int | None:
    """One tuning-file entry, validated: a positive int passes through,
    an absent key is None, and a MALFORMED value (bench never writes one,
    but a hand-edited file might) is ignored with the same
    once-per-process warning discipline as the env path — a typo'd
    tuning file must degrade to the shipped defaults, never crash a
    trace or silently dispatch off a garbage threshold."""
    global _warned_malformed_tuning
    v = _flash_tuning().get(key)
    if v is None:
        return None
    if isinstance(v, bool) or not isinstance(v, int) or v <= 0:
        if not _warned_malformed_tuning:
            _warned_malformed_tuning = True
            import warnings

            from tpuflow import obs

            warnings.warn(
                f"flash tuning entry {key}={v!r} is not a positive "
                "integer; ignoring it",
                stacklevel=3,
            )
            obs.event("warn.flash_min_seq_malformed", value=repr(v))
        return None
    return v


def resolve_attention_impl(
    impl: str, seq_len: int, *, needs_bwd: bool = True,
    backend: str | None = None,
) -> str:
    """Resolve ``impl='auto'`` to a concrete implementation for one
    (backend, seq_len, path) combination — factored out of ``attention``
    so the dispatch choice is unit-testable without a TPU. Non-'auto'
    impls pass through unchanged. ``needs_bwd`` selects which measured
    crossover applies: the fwd+bwd threshold for calls that will be
    differentiated (training), the fwd-only threshold for pure-inference
    forwards (decode prefill) — see ``_flash_min_seq``."""
    if impl != "auto":
        return impl
    backend = backend if backend is not None else jax.default_backend()
    if backend == "tpu" and seq_len >= _flash_min_seq(needs_bwd=needs_bwd):
        return "flash"
    return "xla"


def _flash_runs(q_shape, tk: int, causal: bool) -> bool:
    """Whether the flash kernels can run this call where it is traced:
    'auto' never picks a kernel that cannot. Two things stop them. The
    shape (``flash_tiles``): a 600-token prefill does not tile the 256-row
    score tiles, gpt2-xl's 25 heads of 64 do not pair into 128-lane tiles.
    And the placement: a Mosaic kernel is opaque to the partitioner, and
    jax refuses to lower one inside a program partitioned over several
    devices ("wrap the call in a shard_map"), so under a mesh of more
    than one device 'auto' stays with XLA, as it did on four chips before
    ISSUE 31 (PERF.md §7: the four-chip issue's first change)."""
    from tpuflow.ops.flash_attention import flash_tiles
    from tpuflow.parallel.sharding import active_mesh

    mesh = active_mesh()
    if mesh is not None and mesh.size > 1:
        return False
    _, tq, h, d = q_shape
    return flash_tiles(tq, tk, h, d, causal=causal)


def attention(q, k, v, *, causal: bool = True, impl: str = "xla",
              needs_bwd: bool = True):
    """Dispatch to the selected implementation (see module docstring).

    ``impl='auto'`` picks by measured crossover: flash only on TPU, only
    for a call the kernels can run (``_flash_runs``: a shape they tile,
    on one device), at T >= the resolved threshold — the
    fwd+bwd threshold when ``needs_bwd`` (TPUFLOW_FLASH_MIN_SEQ /
    tuning-file ``flash_min_seq`` / 1024), else the fwd-only threshold
    (TPUFLOW_FLASH_MIN_SEQ_FWD / ``flash_min_seq_fwd`` / 1024); the
    defaults are the chip calls of PR 31, above — and XLA everywhere
    else; CPU always takes XLA (flash there is interpret-mode, for tests
    only).
    """
    if impl == "auto":
        # NB: resolved at trace time — under jit the choice is baked into
        # the compiled program for each shape; changing the env var after
        # compilation does not retune existing executables.
        impl = resolve_attention_impl(
            "auto", q.shape[1], needs_bwd=needs_bwd
        )
        if impl == "flash" and not _flash_runs(q.shape, k.shape[1], causal):
            impl = "xla"
    if impl == "xla":
        fn = xla_attention
    elif impl == "flash":
        from tpuflow.ops.flash_attention import flash_attention as fn
    elif impl == "ring":
        from tpuflow.parallel.ring_attention import ring_attention as fn
    elif impl == "ulysses":
        from tpuflow.parallel.ulysses import ulysses_attention as fn
    else:
        raise KeyError(
            f"unknown attention impl {impl!r}; use xla|flash|ring|ulysses"
        )
    # One scope whatever the implementation: the profiler's device time
    # under `attn_core` follows attention across a change of kernel.
    with jax.named_scope("attn_core"):
        return fn(q, k, v, causal=causal)
