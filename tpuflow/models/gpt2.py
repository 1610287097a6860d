"""GPT-2 causal language model in Flax — the FSDP acceptance-config model.

The driver acceptance configs name "GPT-2-medium FSDP → pjit fully-sharded
checkpoint (multi-host v5e-32)" (BASELINE.md config 5); the reference repo has
no transformer at all, so this is a TPU-first design, not a translation:
bf16 activations on the MXU, attention behind the pluggable ``tpuflow.ops``
dispatch ('xla' | Pallas 'flash' | sequence-parallel 'ring'), weights tied
between the token embedding and the LM head, and shapes kept static for jit.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpuflow.ops import attention, paged_pool


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_ctx: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.1
    ln_eps: float = 1e-5  # GPT-2's LayerNorm epsilon (HF-checkpoint parity)
    attn_impl: str = "xla"  # 'auto' | 'xla' | 'flash' | 'ring' | 'ulysses'
    dtype: jnp.dtype = jnp.float32  # activation dtype; bfloat16 on TPU
    # Rematerialize each block on the backward pass (jax.checkpoint): peak
    # activation memory drops from O(n_layer·B·T·C) to O(B·T·C) + one block's
    # intermediates, the standard HBM-for-FLOPs trade for long-context /
    # large-model training on TPU.
    remat: bool = False
    # Selective remat: name of a jax.checkpoint_policies entry controlling
    # WHICH intermediates the block saves vs recomputes. None = save the
    # block's input and, where the Pallas attention kernels ran, their o
    # and lse (``remat_saves``): the recompute then holds no kernel; with
    # any other attention nothing carries those names and only the input
    # is kept. 'nothing_saveable' recomputes the kernel too. The TPU-standard
    # middle ground is 'dots_with_no_batch_dims_saveable': matmul outputs
    # (MXU work) are saved, elementwise/softmax (cheap VPU work, the bulk
    # of activation bytes) recompute — most of the memory win at a
    # fraction of the recompute cost.
    remat_policy: str | None = None
    # Roll the layer stack into one nn.scan'd block: the transformer block is
    # traced/compiled ONCE instead of n_layer times (compile time stops
    # scaling with depth) and params stack along a leading layer axis, which
    # the path+shape sharding rules handle transparently. Checkpoints are not
    # interchangeable between scan and non-scan layouts.
    scan_layers: bool = False
    # Mixture-of-Experts: n_experts > 0 replaces every block's MLP with a
    # Switch-routed expert MLP (tpuflow.models.moe) whose weights shard over
    # the 'expert' mesh axis (expert parallelism).
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2  # load-balance loss coefficient
    # Decode-path (KV-cache) compute dtype. Autoregressive decode is
    # HBM-bandwidth-bound — weights stream as bf16 regardless — so f32
    # compute costs ~nothing and makes decode numerics WIDTH-INDEPENDENT:
    # bf16 rounding of layer outputs differs systematically between a
    # (K+1)-token chunk forward and single-token decode (one bf16 ulp is
    # 0.4%, dwarfing the 1e-7 f32 accumulation noise), which flipped
    # near-tie argmaxes and broke speculative decode's exactness vs plain
    # greedy (r4 on-chip numerics_ok=false; reproduced on CPU-bf16 at
    # scan_layers). None = use ``dtype`` (the old width-dependent
    # behavior, for capacity-critical serving).
    decode_dtype: jnp.dtype | None = jnp.float32
    # KV-cache storage dtype. None = the decode compute dtype above (so
    # exactness-by-default); set bfloat16 to halve cache bytes for long
    # contexts at the cost of the width-dependent rounding amplifier.
    cache_dtype: jnp.dtype | None = None
    # Decode-path (KV-cache, non-prefill) matmul precision. decode_dtype
    # = f32 removed the LAYER-STACK width dependence, but on TPU the
    # MXU's DEFAULT precision still lowers f32 matmuls to bf16 multiply
    # passes whose rounding depends on the program's tiling — i.e. on
    # the chunk WIDTH — so a (K+1)-token verify forward and single-token
    # decode could still argmax-flip near-tie logits (the r5 on-chip
    # speculative numerics_ok=false on BOTH prompt legs while every CPU
    # scenario stayed bit-exact; the suspected ladder-acceptance pad bug
    # was ruled out — acceptance compares argmaxes of ONE forward, see
    # tests/test_speculative.py::test_pad_laden_drafts_stay_exact).
    # 'highest' pins decode-mode matmuls (attention, Dense, LM head) to
    # true f32 — decode is HBM-bandwidth-bound, so the extra MXU passes
    # are ~free. None = platform default (the old behavior, for
    # capacity-critical serving). Prefill keeps DEFAULT precision: it is
    # the one compute-bound decode call and runs at the same width in
    # every decode strategy, so it cannot introduce width-dependent
    # rounding.
    decode_precision: str | None = "highest"
    # Paged KV cache (the serving engine's block-granular layout,
    # ISSUE 11). kv_pages > 0 switches slot-mode decode calls that pass
    # a ``page_table`` to a POOLED cache: instead of one contiguous
    # (B, n_ctx, H, D) row per slot, the cache is a fixed
    # (kv_pages, kv_page_size, H * D) pool and each slot's logical row is
    # scattered across the pages its (B, up to n_ctx/kv_page_size) table
    # names. kv_page_size must divide n_ctx. Page 0 is the engine's
    # TRASH page: out-of-range writes and dead slots (zeroed tables)
    # land there and nothing ever reads it, so a freed page can be
    # re-allocated to a new request without the old slot's frozen
    # garbage write chasing it. Training/scoring/solo-generate forwards
    # never consult these fields.
    kv_pages: int = 0
    kv_page_size: int = 0

    def compute_dtype(self, decode: bool):
        """Activation/compute dtype for this forward: ``decode_dtype``
        on the KV-cache path (width-independent f32 by default — see the
        field comment), ``dtype`` for training/scoring forwards."""
        if decode and self.decode_dtype is not None:
            return self.decode_dtype
        return self.dtype

    def kv_cache_dtype(self):
        """Storage dtype of the KV cache (``cache_dtype`` override, else
        the decode compute dtype)."""
        if self.cache_dtype is not None:
            return self.cache_dtype
        return self.compute_dtype(decode=True)

    def matmul_precision(self, decode: bool):
        """``jax.lax.Precision`` for this forward's matmuls: the pinned
        ``decode_precision`` on the KV-cache (non-prefill) path, else
        None (platform default). See the field comment for why decode
        needs width-independent rounding."""
        if decode and self.decode_precision:
            import jax

            return jax.lax.Precision(self.decode_precision.lower())
        return None

    @classmethod
    def small_test(cls, **kw) -> "GPT2Config":
        """Tiny config for tests (fast CPU compile)."""
        kw = {
            "vocab_size": 512,
            "n_ctx": 128,
            "n_embd": 128,
            "n_layer": 2,
            "n_head": 4,
            **kw,
        }
        return cls(**kw)

    @classmethod
    def medium(cls, **kw) -> "GPT2Config":
        """GPT-2-medium (355M): 24 layers, 1024 hidden, 16 heads."""
        kw = {"n_embd": 1024, "n_layer": 24, "n_head": 16, **kw}
        return cls(**kw)

    @classmethod
    def from_preset(
        cls,
        preset: str,
        *,
        attn_impl: str = "auto",
        seq_len: int = 64,
        stage_axis: int = 1,
        n_experts: int = 0,
        dtype=None,
    ) -> "GPT2Config":
        """The flows' preset table: ``test`` (tiny, fast CPU compile),
        ``gpt2`` (124M), ``medium`` (355M). Full-size presets scan the
        layer stack (compile time independent of depth) and rematerialize
        blocks (activation memory independent of depth) — the TPU-first
        defaults for real training. ``dtype`` overrides the ACTIVATION
        dtype (params/optimizer stay f32 — flax's param_dtype default):
        ``jnp.bfloat16`` is the standard TPU mixed-precision recipe (MXU
        operands in bf16, f32 master weights, f32 softmax/CE via the
        model's float32 loss head)."""
        extra = {} if dtype is None else {"dtype": dtype}
        if preset == "medium":
            return cls.medium(
                attn_impl=attn_impl, scan_layers=True, remat=True,
                n_experts=n_experts, **extra,
            )
        if preset == "gpt2":
            return cls(
                attn_impl=attn_impl, scan_layers=True, remat=True,
                n_experts=n_experts, **extra,
            )
        if preset == "test":
            return cls.small_test(
                attn_impl=attn_impl,
                n_ctx=max(128, seq_len),
                # Pipeline parallelism requires the scan-stacked block
                # layout (one leading layer axis to shard over 'stage').
                scan_layers=stage_axis > 1,
                n_layer=max(2, stage_axis),
                n_experts=n_experts,
                **extra,
            )
        raise ValueError(
            f"unknown preset {preset!r}; available: test, gpt2, medium"
        )


def remat_saves(cfg: GPT2Config) -> tuple[str, ...]:
    """The named values a rematerialised block keeps beside its input:
    the Pallas attention kernels' o and lse (named where
    ``ops/flash_attention.py``'s vjp forward makes them) under policy-less
    remat and under 'dots'; nothing with remat off or under a
    ``jax.checkpoint_policies`` name, which decides alone."""
    from tpuflow.ops.flash_attention import RESIDUAL_NAMES

    if cfg.remat and cfg.remat_policy in (None, "", "dots"):
        return RESIDUAL_NAMES
    return ()


def _masked_attention(q, k, v, valid, precision=None):
    """Masked softmax attention, float32 statistics (bf16-safe), static
    shapes. ``valid`` broadcasts against the (B, H, Tq, Tk) score matrix.
    Fully-masked query rows (a left-pad column whose every key is invalid)
    degrade to a uniform softmax over the -1e30 constants — finite garbage
    that no real query ever attends to, so it stays isolated.
    ``precision`` pins the einsum matmul precision (the decode path
    passes Precision.HIGHEST for width-independent MXU rounding)."""
    D = q.shape[-1]
    with jax.named_scope("attn_core"):
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
            precision=precision,
        ) * scale
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", p, v.astype(jnp.float32), precision=precision
        ).astype(q.dtype)


def _left_pad_attention(q, k, v, pad_lens):
    """Causal attention over a LEFT-padded (B, T, H, D) batch: key columns
    ``< pad_lens[b]`` are masked out of row b."""
    T = q.shape[1]
    pos = jnp.arange(T)
    valid = (pos[None, :] <= pos[:, None])[None, None]  # causal (T, T)
    valid = valid & (pos[None, None, None, :] >= pad_lens[:, None, None, None])
    return _masked_attention(q, k, v, valid)


class Block(nn.Module):
    """Pre-LN transformer block: LN → MHA → residual, LN → MLP → residual.

    ``decode=True`` switches the attention to a fixed-size KV cache
    (``cache`` collection: ``cached_key``/``cached_value`` (B, n_ctx, H, D)
    + scalar ``cache_index``): the incoming T tokens are written at the
    current index and q attends over the cache through a static-shape mask
    (position ≤ query position) — one compilation for prefill (T=prompt)
    and one for single-token decode (T=1), XLA-friendly throughout. The
    reference has no generation path at all (its predictor is one
    classifier forward, my_ray_module.py:275-284); this is the LM-family
    completion of the batch-inference capability (SURVEY.md §2b D12).
    """

    config: GPT2Config

    @nn.compact
    def __call__(self, x, train: bool, decode: bool = False, pad_lens=None,
                 prefill: bool = False, slot_index=None, page_table=None,
                 layer=None):
        cfg = self.config
        B, T, C = x.shape
        head_dim = cfg.n_embd // cfg.n_head
        # Decode-path compute dtype (f32 by default: width-independent
        # numerics on the HBM-bound path; see GPT2Config.decode_dtype).
        # Prefill keeps the training dtype — prompt ingestion runs with
        # the SAME width in every decode strategy, so it cannot introduce
        # width-dependent rounding, and it is the one decode-mode call
        # that is compute-bound (TxT attention over the whole prompt).
        dt = cfg.compute_dtype(decode and not prefill)
        # Width-independent decode rounding: pin MXU precision on the
        # non-prefill decode path (see GPT2Config.decode_precision).
        prec = cfg.matmul_precision(decode and not prefill)

        h = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=dt, name="ln_1")(x)
        qkv = nn.Dense(
            3 * cfg.n_embd, dtype=dt, precision=prec, name="c_attn"
        )(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, cfg.n_head, head_dim)
        k = k.reshape(B, T, cfg.n_head, head_dim)
        v = v.reshape(B, T, cfg.n_head, head_dim)
        if decode:
            a = self._cached_attention(
                q, k, v, pad_lens, prec, slot_index, page_table, layer
            )
        elif pad_lens is not None:
            # Ragged (LEFT-padded) batch without a cache — the scoring path:
            # pad columns are masked out of every key set and real positions
            # are row-shifted, so a padded forward is token-exact vs a dense
            # per-row forward (tpuflow.infer.score on mixed-length batches).
            a = _left_pad_attention(q, k, v, pad_lens)
        else:
            a = attention(q, k, v, causal=True, impl=cfg.attn_impl)
        a = a.reshape(B, T, cfg.n_embd)
        a = nn.Dense(cfg.n_embd, dtype=dt, precision=prec, name="c_proj")(a)
        a = nn.Dropout(cfg.dropout, deterministic=not train)(a)
        x = x + a

        h = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=dt, name="ln_2")(x)
        if cfg.n_experts > 0:
            from tpuflow.models.moe import MoEMLP

            h = MoEMLP(
                d_model=cfg.n_embd,
                d_ff=4 * cfg.n_embd,
                n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor,
                aux_weight=cfg.moe_aux_weight,
                dtype=dt,
                name="moe",
            )(h, train)
        else:
            h = nn.Dense(
                4 * cfg.n_embd, dtype=dt, precision=prec, name="mlp_fc"
            )(h)
            h = nn.gelu(h)
            h = nn.Dense(
                cfg.n_embd, dtype=dt, precision=prec, name="mlp_proj"
            )(h)
        h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
        return x + h

    def _paged_attention(self, q, k, v, pad_lens, precision, slot_index,
                         page_table, layer=None):
        """Paged (block-pooled) KV-cache attention — the serving engine's
        per-row cache positions over a page pool (ISSUE 11).

        The cache is ONE (kv_pages, kv_page_size, H * D) pool per layer,
        shared by every slot: a token's K (or V) is one vector of all its
        heads, because the chip lays a leaf out page-major, as every
        program here indexes it, only when its minor axis fills whole
        128-lane rows (``ops/paged_pool.py``; with (H, D) = (16, 64) as
        the tail the page axis went to the lanes and every decode block
        and insert copied the pool there and back). ``page_table`` (B, W)
        int32, W at most ``n_ctx / page_size``, maps the first ``W *
        page_size`` logical cache columns of each row onto pool pages, and
        is threaded
        through the decode program as DATA — admissions, evictions and
        prefix-page sharing never change a shape, so the engine's
        never-recompile contract extends to page management. The
        table's own width is the width of the read: the caller passes
        the columns ``[:W]`` that hold every row's frontier (the engine
        picks W from a short ladder, ``infer.serve.decode_ladder``) and
        nothing beyond them is gathered, masked or multiplied.

        Threading: the pool is one buffer that is only ever indexed into,
        never sliced, restacked or copied. Without a layer scan each
        block owns its (kv_pages, page_size, H * D) leaf. Under
        ``scan_layers`` the leaf is the whole (n_layer, kv_pages,
        page_size, H * D) stack, CARRIED through the layer loop
        (``GPT2.__call__``: ``variable_carry``), and ``layer`` — this
        iteration's index, the loop's scanned input — offsets every
        index into the stack flattened over (layer, page): a step
        writes B*T rows of H * D per layer for K and for V (16 x 4 KB
        at the serving cell's size), whatever the pool holds.

        Writes: row b's T new k/v land at logical columns
        ``slot_index[b] + t``, each routed to
        ``(layer * kv_pages + table[b, col // ps]) * ps + col % ps`` of
        the flattened pool, in one scatter.
        Columns beyond the table (>= W * page_size: a dying row's
        overshoot past n_ctx, or a row the caller did not size the
        table for) and dead slots (tables zeroed by the engine) route
        to the layer's page 0 — the reserved TRASH page nothing ever
        reads — so a page freed and re-allocated to a new request can
        never be corrupted by its old slot's frozen garbage write.

        Reads: each row gathers its logical (W * page_size, H * D) view
        through its table (pages ``layer * kv_pages + table[b]``, one
        gather; the gathered rows, not the pool, are reshaped to (H, D))
        and runs masked attention over it — columns
        ``[pad_lens[b], slot_index[b] + t]`` only.
        Masked columns may be backed by the trash page or a stale page:
        their scores are the -1e30 constant either way, so the gathered
        garbage never reaches a real query. The gathered bytes are
        B x W pages a layer for K and for V: the attention's HBM
        traffic follows the table it is given, not ``n_ctx``.
        """
        cfg = self.config
        B, T, H, D = q.shape
        ps = cfg.kv_page_size
        n_pages = cfg.kv_pages
        width = page_table.shape[1] * ps  # positions this call reads
        cdt = cfg.kv_cache_dtype()
        stack = () if layer is None else (cfg.n_layer,)
        first_page = 0 if layer is None else layer * n_pages
        leaf = stack + (n_pages, ps, paged_pool.token_width(H * D))
        ck = self.variable("cache", "cached_key", jnp.zeros, leaf, cdt)
        cv = self.variable("cache", "cached_value", jnp.zeros, leaf, cdt)
        # Created (never read/advanced) so the paged cache pytree keeps
        # the structure of a row cache — the engine's page-insert
        # tree_maps the two together.
        self.variable(
            "cache", "cache_index", lambda: jnp.zeros(stack, jnp.int32)
        )
        pos, flat = paged_pool.token_slots(
            page_table, slot_index, T, ps, first_page
        )
        with jax.named_scope("kv_write"):
            ck.value = paged_pool.write_tokens(ck.value, flat, k)
            cv.value = paged_pool.write_tokens(cv.value, flat, v)
        with jax.named_scope("kv_read"):
            pages = first_page + page_table
            k_all = paged_pool.read_rows(ck.value, pages, (H, D))
            v_all = paged_pool.read_rows(cv.value, pages, (H, D))
        k_pos = jnp.arange(width)
        valid = k_pos[None, None, None, :] <= pos[:, None, :, None]
        if pad_lens is not None:
            valid = valid & (
                k_pos[None, None, None, :] >= pad_lens[:, None, None, None]
            )
        return _masked_attention(q, k_all, v_all, valid, precision=precision)

    def _cached_attention(self, q, k, v, pad_lens=None, precision=None,
                          slot_index=None, page_table=None, layer=None):
        """Fixed-size KV-cache attention (decode mode).

        Writes the new k/v at ``cache_index`` and attends q over the whole
        cache behind a mask — shapes stay static for jit, the cache updates
        ride ``lax.dynamic_update_slice`` (no data-dependent shapes), and
        the O(n_ctx) masked attention is the HBM-bandwidth-optimal form for
        single-token decode on TPU (a (1, n_ctx) GEMV per head on the MXU).

        ``pad_lens`` (B,) marks rows as LEFT-padded: cache columns
        ``< pad_lens[b]`` are invisible to every query of row b (ragged
        prompt batches; tpuflow.infer.generate ``prompt_lens``).

        ``slot_index`` (B,) with ``page_table`` switches to PER-ROW cache
        positions in the page pool (the continuous-batching serving
        engine, tpuflow.infer.serve; ``_paged_attention``). The scalar
        ``cache_index`` is then not consulted or advanced: the engine
        owns per-slot lengths.

        Multi-token calls: a fresh-cache prefill (``start == 0``, no pads)
        takes the T x T fast path through the pluggable attention dispatch;
        any other multi-token call — chunked prefill at ``start > 0``, or a
        padded prefill — runs masked attention over the whole cache, which
        is exact for every (start, pad) combination (``lax.cond`` picks the
        branch at runtime, so both compile into the one program).
        """
        cfg = self.config
        B, T, H, D = q.shape
        if slot_index is not None:
            if page_table is None:
                raise ValueError(
                    "slot_index passed without page_table: per-row cache "
                    "positions live in the page pool alone (the "
                    "contiguous slot rows went with PR 32)"
                )
            if cfg.kv_pages <= 0:
                raise ValueError(
                    "page_table passed but the config declares no page "
                    "pool — set kv_pages/kv_page_size (the serving "
                    "engine clones its decode model with them)"
                )
            return self._paged_attention(
                q, k, v, pad_lens, precision, slot_index, page_table, layer
            )
        cdt = cfg.kv_cache_dtype()
        ck = self.variable(
            "cache",
            "cached_key",
            jnp.zeros,
            (B, cfg.n_ctx, H, D),
            cdt,
        )
        cv = self.variable(
            "cache",
            "cached_value",
            jnp.zeros,
            (B, cfg.n_ctx, H, D),
            cdt,
        )
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        start = idx.value
        with jax.named_scope("kv_write"):
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(cdt), (0, start, 0, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(cdt), (0, start, 0, 0)
            )
        idx.value = start + T

        def cache_attention():
            # Key position k is visible to query position start+t iff
            # k <= start+t (and, for left-padded rows, k >= pad_lens[b]).
            q_pos = start + jnp.arange(T)[:, None]
            k_pos = jnp.arange(cfg.n_ctx)[None, :]
            valid = (k_pos <= q_pos)[None, None]
            if pad_lens is not None:
                valid = valid & (
                    k_pos[None, None] >= pad_lens[:, None, None, None]
                )
            return _masked_attention(
                q, ck.value, cv.value, valid, precision=precision
            )

        if T > 1:
            # Fresh-cache prefill (start == 0) takes an exact T x T path —
            # the pluggable dispatch when dense, the left-padded masked
            # form when ragged — instead of softmaxing over n_ctx - T dead
            # cache columns; warm-cache (chunked) prefill takes the general
            # cache path. Runtime branch: start is traced.
            fast = (
                (lambda: attention(
                    q, k, v, causal=True, impl=cfg.attn_impl
                ).astype(q.dtype))
                if pad_lens is None
                else (lambda: _left_pad_attention(q, k, v, pad_lens))
            )
            return jax.lax.cond(start == 0, fast, cache_attention)
        return cache_attention()


class _ScanBlock(nn.Module):
    """Scan-body adapter: (carry, broadcast train/decode) → (carry, no ys).
    ``layer`` is the one scanned input: the iteration's index, given only
    where the paged pool is carried through the loop."""

    config: GPT2Config

    @nn.compact
    def __call__(self, x, train: bool, decode: bool = False, pad_lens=None,
                 prefill: bool = False, slot_index=None, page_table=None,
                 layer=None):
        return (
            Block(self.config, name="block")(
                x, train, decode, pad_lens, prefill, slot_index, page_table,
                layer,
            ),
            None,
        )


class GPT2(nn.Module):
    """Token ids (B, T) int32 → logits (B, T, vocab). LM head tied to wte."""

    config: GPT2Config = GPT2Config()

    @nn.compact
    def __call__(
        self, tokens, *, train: bool = False, decode: bool = False,
        pad_lens=None, prefill: bool = False, slot_index=None,
        page_table=None,
    ):
        """``pad_lens`` (B,) int32 marks LEFT-padded rows: row b's first
        ``pad_lens[b]`` columns are padding — their positions clamp to 0,
        and every attention masks them out of the key set (ragged prompt
        generation / scoring; tpuflow.infer). ``prefill=True`` marks a
        decode-mode call that ingests the prompt: it keeps the training
        compute dtype (same-width in every decode strategy, so no
        width-dependent rounding; and it is the compute-bound decode
        call) while verify chunks and single-token steps run in
        ``decode_dtype``. ``slot_index`` (B,) int32 with ``page_table``
        (B, up to n_ctx/kv_page_size) int32 switches decode mode to PER-ROW
        cache positions in the PAGED cache pool (the serving engine;
        ``kv_pages``/``kv_page_size`` config fields): row b writes/reads
        at its own logical column, routed through the table onto shared
        pool pages (Block._paged_attention), positions come from
        ``slot_index - pad_lens``, and the model-level ``pos_index`` is
        neither consulted nor advanced."""
        cfg = self.config
        B, T = tokens.shape
        if pad_lens is not None:
            pad_lens = jnp.asarray(pad_lens, jnp.int32)
        if slot_index is not None:
            slot_index = jnp.asarray(slot_index, jnp.int32)
        if page_table is not None:
            page_table = jnp.asarray(page_table, jnp.int32)
        wte = self.param(
            "wte",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.n_embd),
            jnp.float32,
        )
        wpe = self.param(
            "wpe",
            nn.initializers.normal(0.01),
            (cfg.n_ctx, cfg.n_embd),
            jnp.float32,
        )
        if decode:
            # Autoregressive mode: positions continue from the model-level
            # cache index (the blocks keep their own KV indices in the same
            # 'cache' collection; see Block._cached_attention).
            pos = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            start = pos.value
            if slot_index is None:
                pos.value = start + T
            if slot_index is not None:
                # Slot mode: per-row positions from the engine's per-slot
                # lengths (pad columns shift them down, as in ragged
                # decode). The shared pos_index stays untouched.
                base = slot_index[:, None] + jnp.arange(T)[None, :]
                if pad_lens is not None:
                    base = base - pad_lens[:, None]
                positions = jnp.clip(base, 0, cfg.n_ctx - 1)
                pe = wpe[positions]  # (B, T, C)
            elif pad_lens is not None:
                # Left-padded rows: real positions shift down by the row's
                # pad count (clamped — pad columns read position 0, whose
                # output real tokens never attend to).
                positions = jnp.clip(
                    start + jnp.arange(T)[None, :] - pad_lens[:, None],
                    0,
                    cfg.n_ctx - 1,
                )
                pe = wpe[positions]  # (B, T, C)
            else:
                pe = jax.lax.dynamic_slice(
                    wpe, (start, jnp.int32(0)), (T, cfg.n_embd)
                )
        elif pad_lens is not None:
            positions = jnp.clip(
                jnp.arange(T)[None, :] - pad_lens[:, None], 0, cfg.n_ctx - 1
            )
            pe = wpe[positions]
        else:
            pe = wpe[:T]
        dt = cfg.compute_dtype(decode and not prefill)
        x = wte[tokens].astype(dt) + pe.astype(dt)
        from tpuflow.parallel.sharding import pin_batch

        # Keep the activations split on the batch whatever layout FSDP
        # gave the embedding tables (see pin_batch).
        x = pin_batch(x)
        x = nn.Dropout(cfg.dropout, deterministic=not train)(x)
        def remat_wrap(mod):
            cp = jax.checkpoint_policies
            policy = cp.save_only_these_names(*remat_saves(cfg))
            if cfg.remat_policy == "dots":
                # The ISSUE 10 selector's middle ground: every MXU dot
                # output and the attention kernel's residual are saved,
                # so the backward recomputes only cheap elementwise and
                # softmax work (the zero-recompute mode is remat OFF,
                # selector 'none').
                policy = cp.save_from_both_policies(
                    cp.dots_with_no_batch_dims_saveable, policy
                )
            elif cfg.remat_policy:
                try:
                    policy = getattr(cp, cfg.remat_policy)
                except AttributeError:
                    raise ValueError(
                        f"unknown remat_policy {cfg.remat_policy!r}; valid "
                        "names are the jax.checkpoint_policies attributes"
                    ) from None
            # Args (with the module at 0): x=1, train=2, decode=3,
            # pad_lens=4, prefill=5, slot_index=6, page_table=7, layer=8.
            # train/decode/prefill are Python bools that steer tracing —
            # static. pad_lens, slot_index, page_table and layer are DATA
            # arrays (tracers during ragged/slot/paged decode): marking
            # pad_lens static, as (2, 3, 4) once did, crashed every
            # remat=True decode-mode call with TracerBoolConversionError.
            return nn.remat(mod, static_argnums=(2, 3, 5), policy=policy)

        if cfg.scan_layers:
            body = remat_wrap(_ScanBlock) if cfg.remat else _ScanBlock
            call = (x, train, decode, pad_lens, prefill, slot_index,
                    page_table)
            # The paged pool rides the layer loop as its CARRY, whole (a
            # (n_layer, kv_pages, page_size, H * D) leaf for K and for V),
            # and each iteration indexes into it with its own number, the
            # loop's one scanned input (Block._paged_attention). Scanned
            # in and out by layer instead, every iteration would slice
            # its pool out of the stack and write it back into another
            # stack. Row caches are scanned by layer, and so is a paged
            # cache in the one call that creates it: each layer makes
            # its leaf and the scan stacks them, as it does params.
            carried = page_table is not None and self.has_variable(
                "cache", "h"
            )
            # 'losses' must be declared or nn.scan silently DROPS the
            # per-layer sown values (the MoE load-balance aux loss).
            variable_axes = {"params": 0, "losses": 0}
            in_axes = nn.broadcast
            if carried:
                in_axes = (nn.broadcast,) * (len(call) - 1) + (0,)
                call += (jnp.arange(cfg.n_layer),)
            else:
                variable_axes["cache"] = 0
            blocks = nn.scan(
                body,
                variable_axes=variable_axes,
                variable_carry="cache" if carried else False,
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layer,
                in_axes=in_axes,
            )
            x, _ = blocks(cfg, name="h")(*call)
        else:
            block_cls = remat_wrap(Block) if cfg.remat else Block
            for i in range(cfg.n_layer):
                x = block_cls(cfg, name=f"h{i}")(
                    x, train, decode, pad_lens, prefill, slot_index,
                    page_table,
                )
        x = nn.LayerNorm(epsilon=cfg.ln_eps, dtype=dt, name="ln_f")(x)
        if self.has_variable("quant", "wte_q"):
            # Native int8 LM head (ISSUE 9): the fused-native quantizer
            # (tpuflow.infer.quant mode='mxu') supplies an int8 view of
            # the tied wte with PER-VOCAB-ROW scales as its own 'quant'
            # collection — the 'params' tree keeps the fp structure this
            # module declares, so checkpoints and shardings never see a
            # fork. Decode streams the (vocab, n_embd) head — a third of
            # GPT-2-124M's bytes — as int8, and the integer contraction
            # is exact, hence width-independent on the MXU: the
            # decode_precision pinning below exists to fix exactly the
            # rounding an int8 matmul cannot exhibit.
            from tpuflow.ops.int8_matmul import int8_matmul

            head = self.get_variable("quant", "wte_q")
            with jax.named_scope("lm_head"):
                return int8_matmul(
                    x, head.q, head.scale, w_contract_last=True,
                    out_dtype=jnp.float32,
                )
        # Weight-tied LM head; logits come straight out of the MXU's f32
        # accumulator (preferred_element_type) — never rounded through
        # bf16. The old einsum→bf16→f32 path collapsed near-tie logits
        # onto equal bf16 values, and argmax over those flipped between
        # the chunked verify forward and single-token decode (one part of
        # the r4 on-chip speculative numerics_ok=false; decode_dtype
        # handles the layer-stack part). f32 logits also feed a stable
        # softmax/CE in training.
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "btc,vc->btv",
                x,
                wte.astype(dt),
                preferred_element_type=jnp.float32,
                # Decode non-prefill: HIGHEST precision so the logits'
                # rounding is width-independent on the MXU too (the f32
                # accumulator alone does not fix the bf16 multiply passes
                # DEFAULT precision lowers f32 operands to).
                precision=cfg.matmul_precision(decode and not prefill),
            )
