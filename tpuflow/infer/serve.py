"""Continuous-batching serving engine: persistent slot-based KV decode
with interleaved chunked prefill.

The batch predictor (``tpuflow.infer.engine``) compiles one KV program
per batch and decodes lockstep: aggregate tokens/s collapses the moment
requests have unequal lengths or arrive at different times, because every
row waits for the slowest and every new shape recompiles. TPU serving
throughput comes from the opposite design (the Gemma-on-TPU serving
comparison, PAPERS.md): keep ONE persistently-compiled decode program
saturated and move requests through it independently.

Shape of the engine:

- **Slots over one KV cache.** One fixed set of ``max_slots`` rows
  owned by one decode-block program. Each slot carries its own
  ``live`` / ``length`` / ``pad`` / ``remaining`` state in (S,) host
  arrays — admissions, generation, and evictions are DATA, never a new
  shape, so nothing recompiles. The per-row cache positions ride the model's
  ``slot_index`` + ``page_table`` decode path (``GPT2.__call__``): row b
  writes its k/v at its own column of its own pages and its queries see
  ``[pad[b], length[b]]`` only, so a reused page's stale columns stay
  invisible.

- **Chunked prefill as the admission path.** A waiting request is
  admitted by LEFT-padding its prompt to a small set of bucket widths
  (``pad_to`` semantics: a handful of prefill programs compile, ever)
  and running ``chunked_prefill`` on a (1, W) row — bounding peak
  attention memory to O(chunk x n_ctx) — then a jitted insert writes the
  row's cache into the slot's pages. Prefill interleaves with decode blocks
  at the scheduler loop, the continuous-batching core.

- **Decode blocks.** Between admissions the engine runs the persistent
  decode program: a ``lax.scan`` of ``decode_block`` single-token steps
  with per-row eos / budget / capacity freezing inside the program (one
  host sync per BLOCK, not per token). A block reads what is live: its
  operands are the group's live slots, padded with dead ones to a row
  count of a short ladder (``decode_ladder``: ``max_slots`` / 4, / 2,
  / 1, and no step of more than 8 rows), and the page-table columns that hold the longest live row plus
  the block (all of ``n_ctx``, or half of it at the smallest row
  count). The host picks the shape from the numpy state it already
  holds (``_decode_rung``) and merges the results back by slot index;
  each (rows, pages) shape is one more entry of the one jitted
  function's cache, four in all up to 16 slots. Greedy decoding; ``decode_precision`` (PR 4)
  makes batched decode width-independent, so every request's tokens are
  exactly what a solo ``generate()`` of its prompt produces.

- **AOT warm path.** ``warmup()`` routes through
  ``maybe_enable_compile_cache`` and executes the decode program at
  every shape of its ladder, the insert, and every prefill bucket
  once, so a restarted server pays cache loads instead of compiles.
  ``compile_stats()`` exposes the jit cache sizes (``decode`` = the
  ladder's size); after warmup they must never grow — pinned by
  tests/test_serve.py.

- **Per-request int8 (ISSUE 9).** ``TPUFLOW_SERVE_QUANT`` (or the
  ``quant=`` ctor arg) arms a SECOND numeric path: the engine quantizes
  the params once (``tpuflow.infer.quant``, fused-native W8A8 by
  default — int8 x int8 -> int32 on the MXU through
  ``tpuflow.ops.int8_matmul``) and compiles an int8 decode-block
  program + prefill ladder at ``warmup()`` beside the fp ones. Each
  ``submit(quantize=True|False)`` routes its request to one path; mixed
  requests SHARE the one engine and the one slot cache (the per-slot
  attention window keeps rows independent, so a group's program can
  run with the other group masked out of its live set without touching
  its state). ``compile_stats()`` still never grows after warmup — the
  never-recompile contract covers the quantized program too.

- **Paged KV (ISSUE 11; the only layout since PR 32).** The cache is a
  fixed POOL of ``(n_pages, page_size)`` pages plus a per-slot page
  table threaded through the decode block as data
  (``Block._paged_attention``) — the same "state is data, never shape"
  trick that made slots recompile-free covers page allocation. A pool
  leaf is ``(..., n_pages, page_size, width)``: what a token holds (K
  or V of all heads, a latent vector) as ONE vector, padded to whole
  128-lane rows (``tpuflow.ops.paged_pool``), because the chip lays a
  leaf out page-major, as every program here indexes it, only when its
  minor axis fills whole 128-lane rows (ISSUE 35: with ``(page_size,
  H, D)`` as the tail every decode block and insert copied the whole
  pool to another layout and back). Prefill rows and page sets keep the
  token's own shape; ``serve.pool_pad_fraction`` says what the pad
  costs. What paging buys over a ``(max_slots, n_ctx)`` row a slot:

  * **Admission by token budget.** A request is admitted when its page
    need (``ceil((len + max_new [+ draft slack]) / page_size)``) fits
    the free pool, not when a whole ``n_ctx`` row is free — short
    requests stop stranding HBM, and a full pool applies BACKPRESSURE
    (the request stays queued, never dropped). Capacity checks move
    from the padded bucket width to the REAL prompt length (bucket
    pads no longer eat cache columns: the page insert strips them).
  * **Shared-prefix page reuse.** Prompt pages are content-hashed at
    page granularity (a chain over ``prompt[:(j+1)*page_size]``) into a
    refcounted prefix cache: a request whose prompt starts with an
    already-resident prefix (system prompt, few-shot header) maps those
    pages into its table instead of allocating copies. Pad-invariant kv
    (the left-pad exactness contract) is what makes the reuse sound.
    Idle (refcount-0) prefix pages stay cached until pool pressure
    evicts them LRU-first (``serve.page_evict``).
  * **Per-request speculative decode.** ``TPUFLOW_SERVE_SPEC=K`` (or
    ``speculative=K``) arms an in-program verify block: each live slot
    drafts K tokens on the host (``tpuflow.infer.speculative.
    ngram_draft`` — prompt-lookup, no draft model), ONE batched
    (S, K+1) forward verifies them, and every row commits its own
    accepted prefix + bonus token — per-row frontiers that the solo
    ladder's shared cache index could never allow. Acceptance argmaxes
    are width-safe by construction (``decode_precision='highest'`` from
    PR 4; int8 contractions are integer-exact, PR 9), so engine tokens
    stay bit-equal to solo ``generate()``. ``submit(speculative=False)``
    opts a request out (it rides the plain single-token block).

- **Generation by diffusion over blocks (ISSUE 37).** ``generation=
  {"kind": "block_diffusion", ...}`` swaps the decode program for
  ``_denoise_fn``: a call runs whole blocks of L tokens, each S denoise
  passes over a row's whole block (a pass unmasks L / S positions, so a
  row gains 0 to L tokens a pass) and a commit pass that writes the
  finished block's keys and values. Tokens and forward passes are then
  two counts: ``decode_block`` is passes a call, ``remaining`` and the
  harvest count tokens, the ``serve.decode`` span and the ledger carry
  both (``passes``, ``commit_passes``, ``tokens_per_pass``). Admission
  caches the prompt's whole blocks and opens the first block with the
  tokens left over; ``page_size`` is a multiple of L, so prefix pages
  stay shareable. Speculation, int8, shipped or tiered pages and
  ``eos_id`` raise ``GenerationUnsupported`` with it. Unset, every
  program is what it was.

Knobs: ``TPUFLOW_SERVE_SLOTS`` (default 8), ``TPUFLOW_SERVE_PREFILL_CHUNK``
(default off), ``TPUFLOW_SERVE_BUCKETS`` (comma widths; default a
power-of-two ladder up to ``n_ctx``), ``TPUFLOW_SERVE_DECODE_BLOCK``
(tokens per decode dispatch, default 8), ``TPUFLOW_SERVE_QUANT``
(=1/fused_native/weight_only arms per-request int8; default off),
``TPUFLOW_SERVE_PAGE_SIZE`` (default 16 tokens),
``TPUFLOW_SERVE_PAGES`` (pool size; default
``max_slots * n_ctx / page_size + 1`` — a full row a slot),
``TPUFLOW_SERVE_PREFIX_CACHE`` (=0 disables shared-prefix reuse),
``TPUFLOW_SERVE_SPEC`` (=K arms per-request speculative decode),
``TPUFLOW_SERVE`` (=0 keeps ``GenerationPredictor`` on the legacy
per-batch path), ``TPUFLOW_SERVE_TRACE`` (=0 disarms per-request
lifecycle traces), ``TPUFLOW_SERVE_ACCESS_LOG`` (=0 disarms the
per-request JSONL access log), ``TPUFLOW_SERVE_SLO_TTFT_MS`` /
``TPUFLOW_SERVE_SLO_ITL_MS`` (declared latency SLOs; violations emit
events and a counter).

Telemetry (``serve.*``, catalog-enforced): queue depth, slot occupancy,
per-request TTFT and decode tokens/s, admission/completion events,
prefill/decode spans — riding ``tpuflow.obs`` and the live ``/metrics``
exporter (``tpuflow.obs.export``), watchable via
``tools/tpu_watch.py --follow``.

**Serving observatory (ISSUE 13).** Three host-side layers mirror the
training run observatory; none adds a jitted operand, so
``compile_stats()`` is unchanged after warmup with everything armed:

- **Per-request lifecycle traces.** Every ``ServeRequest`` carries a
  trace of its transitions — submitted, queued (with the backpressure
  reason: ``slots`` or ``pages``), admitted (bucket, pages, shared
  prefix pages), first_token (TTFT), every decode/verify tick it
  participated in (tokens committed, drafts accepted), and exactly one
  terminal (``complete`` with the finish reason, or ``drained`` on the
  SIGTERM path) — mirrored as ``serve.trace`` events and, at the
  terminal, as one line in the ``obs/access.p*.jsonl`` access log that
  ``python -m tpuflow.obs serve-summary <run_dir>`` reads (no jax
  import, works mid-run).
- **Engine-time ledger** (``tpuflow.obs.serve_ledger.ServeLedger``,
  at ``engine.ledger``): every second of serve wall charges to exactly
  one bucket — prefill / decode / verify / insert / host_sched / idle —
  by cursor construction, plus occupancy-weighted decode utilization,
  masked-row waste from the (fp,int8)x(spec,plain) group partition,
  and speculative drafted-vs-accepted economics.
- **SLO accounting.** ``TPUFLOW_SERVE_SLO_TTFT_MS`` /
  ``TPUFLOW_SERVE_SLO_ITL_MS`` declare latency SLOs; a violating
  request emits ``serve.slo_violation`` and bumps the
  ``serve.slo_violations`` counter, and TTFT/ITL percentiles (split by
  numeric path and spec/plain group) ride ``/metrics``, ``/status``,
  and ``tpu_watch --follow``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import math
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from typing import Any

from tpuflow import obs
from tpuflow.obs import device as _device
from tpuflow.obs import profcap as _profcap
from tpuflow.obs import serve_ledger as _ledger
from tpuflow.obs import trace as _reqtrace
from tpuflow.infer.generate import (
    chunked_prefill,
    normalize_prefill_chunk,
    prompt_lens_to_pad_lens,
)
from tpuflow.infer import kv_store as _kvstore
from tpuflow.infer.speculative import ngram_draft
from tpuflow.ops import paged_pool as _pool
from tpuflow.utils import knobs


def _env_int(name: str, default: int, *, minimum: int = 1) -> int:
    """Malformed env values fall to the default (the dispatch_depth
    idiom: a typo'd knob must not crash a server at start)."""
    # tpulint: disable=knob-dynamic -- name is forwarded verbatim from
    # literal call sites, which the string-literal declaration rule
    # still validates; knobs.raw refuses undeclared names at runtime.
    raw = knobs.raw(name)
    if not raw:
        return default
    try:
        return max(int(raw), minimum)
    except ValueError:
        print(
            f"[tpuflow] malformed {name}={raw!r} (want an integer); "
            f"using {default}"
        )
        return default


def resolve_serve_quant(quant=None) -> str | None:
    """Per-request-int8 mode from the explicit ctor arg or
    ``TPUFLOW_SERVE_QUANT``: None = disabled; ``1``/``true`` = the
    fused-native headline mode; any quantization-mode spelling
    (``fused_native``/``mxu``/``weight_only``/``weight``) selects that
    mode. A malformed ENV value warns and arms fused-native anyway (the
    operator asked for int8; silently serving fp would falsify every
    capacity plan built on the knob) — an explicit bad ``quant=`` arg
    raises, the bucket-knob idiom split by blast radius."""
    from tpuflow.infer.quant import canonical_mode

    if quant is None:
        raw = knobs.raw("TPUFLOW_SERVE_QUANT", "").strip().lower()
        if raw in ("", "0", "false", "off"):
            return None
        if raw in ("1", "true", "on"):
            return "mxu"
        try:
            return canonical_mode(raw)
        except ValueError:
            print(
                f"[tpuflow] malformed TPUFLOW_SERVE_QUANT={raw!r} (want "
                "1|fused_native|weight_only); arming fused_native"
            )
            return "mxu"
    if quant is False:
        return None
    if quant is True:
        return "mxu"
    return canonical_mode(quant)


def _env_flag(name: str, default: bool) -> bool:
    # tpulint: disable=knob-dynamic -- name is forwarded verbatim from
    # literal call sites, which the string-literal declaration rule
    # still validates; knobs.raw refuses undeclared names at runtime.
    raw = knobs.raw(name)
    if raw is None or raw == "":
        return default
    return raw.strip().lower() not in ("0", "false", "off")


def resolve_page_size(n_ctx: int, page_size=None) -> int:
    """Page width in tokens. Must divide ``n_ctx`` (the per-slot table is
    a dense ``n_ctx / page_size`` map). An explicit bad arg raises; a
    malformed/indivisible ENV value degrades to the largest divisor of
    ``n_ctx`` at or below the default with a warning (the bucket-knob
    blast-radius split)."""
    explicit = page_size is not None
    from_env = False
    if page_size is None:
        raw = knobs.raw("TPUFLOW_SERVE_PAGE_SIZE")
        if raw:
            try:
                page_size = int(raw)
                from_env = True
            except ValueError:
                print(
                    f"[tpuflow] malformed TPUFLOW_SERVE_PAGE_SIZE={raw!r} "
                    "(want an integer); using the default"
                )
    ps = int(page_size) if page_size is not None else 16
    if explicit:
        if ps < 1 or n_ctx % ps:
            raise ValueError(
                f"page_size must be >= 1 and divide n_ctx={n_ctx}, got {ps}"
            )
        return ps
    want = ps
    ps = max(min(ps, n_ctx), 1)
    while n_ctx % ps:
        ps -= 1
    if ps != want and from_env:
        print(
            f"[tpuflow] TPUFLOW_SERVE_PAGE_SIZE={want} does not divide "
            f"n_ctx={n_ctx}; using {ps}"
        )
    return ps


def resolve_spec_draft(speculative=None) -> int:
    """Per-request speculative draft length: 0 = off. ``True`` means the
    default draft of 4; an int is the draft length itself. The ENV path
    (``TPUFLOW_SERVE_SPEC``) accepts the same spellings, malformed
    values falling to off with a warning."""
    if speculative is None:
        raw = knobs.raw("TPUFLOW_SERVE_SPEC", "").strip().lower()
        if raw in ("", "0", "false", "off"):
            return 0
        if raw in ("1", "true", "on"):
            return 4
        try:
            return max(int(raw), 0)
        except ValueError:
            print(
                f"[tpuflow] malformed TPUFLOW_SERVE_SPEC={raw!r} (want an "
                "integer draft length); speculative decode stays off"
            )
            return 0
    if speculative is False:
        return 0
    if speculative is True:
        return 4
    k = int(speculative)
    if k < 0:
        raise ValueError(f"speculative draft length must be >= 0, got {k}")
    return k


def resolve_serve_role(role=None) -> str:
    """Serving phase this engine advertises (``TPUFLOW_SERVE_ROLE``):
    ``prefill`` takes the router's ship hops, ``decode`` takes
    admissions, ``both`` (the default) is classic colocated serving.
    The role never hard-gates engine behavior — a decode replica must
    still prefill locally when a shipped set is torn — it is placement
    advice the fleet rows export and the router reads. An explicit bad
    arg raises; a malformed ENV value degrades to ``both`` with a
    warning."""
    if role is None:
        raw = (knobs.raw("TPUFLOW_SERVE_ROLE") or "").strip().lower()
        if raw in ("", "both"):
            return "both"
        if raw in ("prefill", "decode"):
            return raw
        print(
            f"[tpuflow] malformed TPUFLOW_SERVE_ROLE={raw!r} (want "
            "prefill|decode|both); using both"
        )
        return "both"
    r = str(role).strip().lower()
    if r not in ("prefill", "decode", "both"):
        raise ValueError(
            f"role must be prefill|decode|both, got {role!r}"
        )
    return r


class GenerationUnsupported(ValueError):
    """``ServeEngine(generation=...)`` met an option that has no meaning
    with it yet (speculation, the int8 path, shipped or tiered KV pages,
    ``eos_id``): raised where the two meet, never served by one-token
    decode in the option's place."""


def resolve_generation(generation, model) -> dict | None:
    """How the engine generates, validated. None is one-token decode: a
    step yields one token a live row. ``{"kind": "block_diffusion",
    "block_length": L, "denoise_steps": S, "unmask": "sequential" |
    "low_confidence", "mask_id": id}`` generates a block of L tokens at a
    time: S denoise passes over the whole block, each unmasking L / S of
    its positions greedily, then a commit pass that writes the finished
    block's keys and values (``ServeEngine._denoise_fn``). What is left
    out defaults to the model's (``config.block_length``,
    ``config.denoise_steps``, ``config.mask_id``); a model whose attention
    mask is built for another block length is refused."""
    if generation is None:
        return None
    g = dict(generation)
    kind = g.pop("kind", None)
    if kind != "block_diffusion":
        raise ValueError(
            f"generation kind must be 'block_diffusion', got {kind!r}"
        )
    cfg = model.config
    model_length = getattr(cfg, "block_length", None)
    out = {
        "kind": kind,
        "block_length": int(g.pop("block_length", model_length or 0)),
        "denoise_steps": int(
            g.pop("denoise_steps", getattr(cfg, "denoise_steps", 1))
        ),
        "unmask": str(g.pop("unmask", "sequential")),
        "mask_id": int(g.pop("mask_id", getattr(cfg, "mask_id", -1))),
    }
    if g:
        raise ValueError(f"unknown generation option(s) {sorted(g)}")
    L, S = out["block_length"], out["denoise_steps"]
    if L < 1 or model_length != L:
        raise ValueError(
            f"generation block_length {L} is not the model's "
            f"({model_length}): its attention mask is block-causal over "
            "that length"
        )
    if S < 1 or L % S:
        raise ValueError(
            f"denoise_steps must divide block_length={L}, got {S}"
        )
    if out["unmask"] not in ("sequential", "low_confidence"):
        raise ValueError(
            f"unmask must be sequential|low_confidence, got {out['unmask']!r}"
        )
    if not 0 <= out["mask_id"] < cfg.vocab_size:
        raise ValueError(f"mask_id {out['mask_id']} is not in the vocabulary")
    return out


class PagePool:
    """Host-side accounting for the paged KV cache: free-list
    allocation, shared-prefix refcounts, and LRU eviction of idle cached
    prefix pages. Pure python/numpy — the DEVICE side only ever sees the
    resulting page tables as data, so this logic is unit-testable with
    zero compiles (tests/test_serve.py).

    Tiered spill (ISSUE 19): with ``tier_cache`` (a
    ``kv_store.TierCache``) and a ``page_reader`` wired, an evicted
    prefix page's CONTENT drops to host DRAM / node-local disk instead
    of being forgotten, and ``acquire`` extends the digest-chain walk
    into the lower tiers — matched lower-tier pages are freshly
    allocated here and reported via :meth:`take_promotions` so the
    engine restores their bytes instead of recomputing prefill. Without
    a tier cache every code path below is byte-identical to PR 11.

    Page 0 is the reserved TRASH page: never allocated, never read.
    Dead slots' zeroed tables and out-of-range writes route there inside
    the decode program (``Block._paged_attention``), which is what makes
    freeing + re-allocating a page safe while its old slot still sits in
    the batch operands.

    Prefix sharing: page j of a prompt is shareable when it is FULLY
    covered by prompt tokens (``(j+1) * page_size <= len``  — decode
    writes start at ``len``, so shared pages are never written) and is
    keyed by the sha1 of the entire prompt prefix through that page
    (causal attention makes page content a pure function of the
    prefix). A matched page's refcount bumps instead of allocating; at
    release, refcount-0 cached pages go IDLE (still matchable) and are
    only reclaimed by LRU eviction under pool pressure
    (``serve.page_evict``)."""

    def __init__(self, n_pages: int, page_size: int,
                 prefix_cache: bool = True, tier_cache=None,
                 page_reader=None):
        if n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the reserved trash "
                f"page), got {n_pages}"
            )
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.prefix_cache = bool(prefix_cache)
        self._free: list[int] = list(range(self.n_pages - 1, 0, -1))
        self._ref: dict[int, int] = {}
        self._hash_to_page: dict[bytes, int] = {}
        self._page_hash: dict[int, bytes] = {}
        self._idle: collections.OrderedDict[int, None] = (
            collections.OrderedDict()
        )
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.evictions = 0
        self.tier = tier_cache
        self._page_reader = page_reader
        self._pending_promote: list[tuple[int, bytes, str]] = []
        self.tier_hits = 0

    @property
    def usable_pages(self) -> int:
        """Pages a single request could ever hold (pool minus trash)."""
        return self.n_pages - 1

    @property
    def free_pages(self) -> int:
        """Pages allocatable right now: truly free + idle-evictable."""
        return len(self._free) + len(self._idle)

    @property
    def allocated_pages(self) -> int:
        """Pages currently held by at least one live request."""
        return len(self._ref)

    def prefix_digests(self, prompt) -> list[bytes]:
        """Chain keys for every FULLY-prompt-covered page, in order."""
        if not self.prefix_cache:
            return []
        p = np.asarray(prompt, np.int32).reshape(-1)
        ps = self.page_size
        return [
            hashlib.sha1(p[: (j + 1) * ps].tobytes()).digest()
            for j in range(p.size // ps)
        ]

    def match_len(self, digests: list[bytes]) -> int:
        """Longest cached prefix-page chain (no side effects)."""
        m = 0
        for d in digests:
            if d not in self._hash_to_page:
                break
            m += 1
        return m

    def can_fit(self, need: int, matched: int) -> bool:
        return need - matched <= self.free_pages

    def acquire(self, prompt, need: int) -> tuple[list[int], int] | None:
        """Map ``need`` pages for a request whose prompt may share a
        cached prefix. Returns ``(page_ids, matched)`` — the first
        ``matched`` ids are shared prefix pages (refcount bumped, no
        write), the rest freshly allocated — or None when the pool
        cannot fit the request (backpressure: caller leaves it queued).
        Newly-allocated full-prompt pages self-register in the prefix
        cache so the NEXT request with this prefix reuses them."""
        digests = self.prefix_digests(prompt)
        matched = min(self.match_len(digests), need)
        self._pending_promote = []
        if self.tier is not None:
            # Tier walk (ISSUE 19): extend the chain into the lower
            # tiers, contiguously from where HBM broke — each hit gets
            # a FRESH page here (registered below like any full-prompt
            # page) whose bytes the engine restores from the tier.
            j = matched
            while j < min(len(digests), need):
                tier = self.tier.locate(digests[j])
                if tier is None:
                    break
                self._pending_promote.append((j, digests[j], tier))
                j += 1
        if not self.can_fit(need, matched):
            self._pending_promote = []
            return None
        self.prefix_lookups += len(digests[:need])
        self.prefix_hits += matched
        ids: list[int] = []
        for d in digests[:matched]:
            pid = self._hash_to_page[d]
            if self._ref.get(pid, 0) == 0:
                self._idle.pop(pid, None)
            self._ref[pid] = self._ref.get(pid, 0) + 1
            ids.append(pid)
        for j in range(matched, need):
            pid = self._alloc_one()
            self._ref[pid] = 1
            ids.append(pid)
            if j < len(digests) and digests[j] not in self._hash_to_page:
                # A fresh full-prompt page becomes the cached copy of
                # its prefix (skip when another page already owns the
                # digest — e.g. the chain broke on an evicted EARLIER
                # page while a later one survived).
                self._hash_to_page[digests[j]] = pid
                self._page_hash[pid] = digests[j]
        return ids, matched

    def take_promotions(self) -> list[tuple[int, bytes, str]]:
        """The last ``acquire``'s lower-tier matches as ``(page_index,
        digest, tier)`` — consumed by the engine, which fetches each
        bundle and writes it back into the pool (serve.tier_promote)."""
        out, self._pending_promote = self._pending_promote, []
        return out

    def _alloc_one(self) -> int:
        if self._free:
            return self._free.pop()
        pid, _ = self._idle.popitem(last=False)  # LRU-first eviction
        d = self._page_hash.pop(pid)
        del self._hash_to_page[d]
        self.evictions += 1
        if self.tier is not None and self._page_reader is not None:
            # Spill instead of forget: the page's bytes drop a tier and
            # stay findable through the bounded digest→tier index (the
            # ISSUE 19 bugfix — an evicted prefix used to be
            # indistinguishable from never-cached).
            tier = self.tier.spill(d, self._page_reader(pid))
            if tier is not None:
                obs.event("serve.tier_spill", page=pid, tier=tier)
        obs.event("serve.page_evict", page=pid)
        return pid

    def release(self, page_ids) -> None:
        """Drop one ownership of each page; refcount-0 cached prefix
        pages go idle (matchable until evicted), private pages go free."""
        for pid in dict.fromkeys(int(p) for p in page_ids):
            self._ref[pid] -= 1
            if self._ref[pid] == 0:
                del self._ref[pid]
                if pid in self._page_hash:
                    self._idle[pid] = None
                    self._idle.move_to_end(pid)
                else:
                    self._free.append(pid)


def default_buckets(n_ctx: int) -> list[int]:
    """Power-of-two prefill-width ladder, topped by ``n_ctx - 1`` (the
    widest ADMITTABLE width: a bucket of n_ctx leaves no cache column for
    even one generated token, since capacity is checked on the padded
    bucket width). The whole compile set for admission prefill."""
    top = max(n_ctx - 1, 1)
    out: list[int] = []
    w = min(16, top)
    while w < top:
        out.append(w)
        w *= 2
    out.append(top)
    return out


# Narrowest decode read, in positions: one lane tile of a score row. Below
# it a narrower read saves nothing a chip can see, and a tiny engine (the
# tests') keeps one width.
_MIN_READ_POSITIONS = 128
# Most rows between one decode shape and the next: a block pays for the
# rows of its shape, live or not (gathers, attention, the head), so with
# 16 rows and then 32 the seventeenth live row cost 40% of a block
# (PERF.md section 6, PR 34).
_MAX_ROW_STEP = 8


def decode_ladder(
    max_slots: int, n_ctx: int, page_size: int
) -> list[tuple[int, int]]:
    """The decode block's operand shapes ``(rows, pages)``, as
    ``default_buckets`` gives the prefill's widths: the row counts
    ``max_slots`` / 4, / 2 and / 1, and between them whatever keeps a
    count within ``_MAX_ROW_STEP`` rows of the one below, at the full
    ``n_ctx / page_size`` pages, and the smallest of them at half the
    pages too; rounded up, shapes that coincide merged, ascending by
    positions read. A decode block runs at the first that holds its live
    rows and their frontier (``ServeEngine._decode_rung``), so this is the
    whole compile set of a numeric path's decode program.

    Few shapes and not a full rows x widths grid, because every shape
    is traced and lowered at every start-up (0.8 s each on the serving
    cell's host, compile cache filled: PERF.md, PR 33), and because
    rows cost more than width: at 4 rows a block of 8 steps takes 62 /
    69 / 71 ms at 512 / 768 / 1,024 positions, at 8 rows 102 ms at
    512. Up to 16 slots that is four shapes; 32 slots get 24 rows
    between 16 and 32, where one more live row cost two fifths of a
    block (PERF.md, PR 34)."""
    pages = n_ctx // page_size
    rows = sorted({max(-(-max_slots * k // 4), 1) for k in (1, 2, 4)})
    for lo, hi in zip(rows, rows[1:]):
        rows += range(lo + _MAX_ROW_STEP, hi, _MAX_ROW_STEP)
    rows.sort()
    least = min(-(-_MIN_READ_POSITIONS // page_size), pages)
    half = max(-(-pages // 2), least)
    shapes = {(rows[0], half)} | {(r, pages) for r in rows}
    return sorted(shapes, key=lambda s: (s[0] * s[1], s))


def resolve_buckets(n_ctx: int, buckets=None) -> list[int]:
    """Bucket widths from the explicit arg, TPUFLOW_SERVE_BUCKETS, or the
    default ladder — validated, deduped, ascending, capped at the widest
    admittable width (``n_ctx - 1``)."""
    if buckets is None:
        raw = knobs.raw("TPUFLOW_SERVE_BUCKETS")
        if raw:
            try:
                buckets = [int(x) for x in raw.split(",") if x.strip()]
            except ValueError:
                print(
                    f"[tpuflow] malformed TPUFLOW_SERVE_BUCKETS={raw!r} "
                    "(want comma-separated ints); using the default ladder"
                )
                buckets = None
    if buckets is None:
        return default_buckets(n_ctx)
    out = sorted({int(b) for b in buckets if 1 <= int(b) <= n_ctx - 1})
    if not out:
        raise ValueError(
            f"no usable prefill bucket in {buckets!r} (need 1 <= b <= "
            f"n_ctx - 1 = {n_ctx - 1})"
        )
    return out


@dataclasses.dataclass
class ServeRequest:
    """One request's lifecycle, owned by the engine that created it."""

    id: int
    prompt: np.ndarray  # (L,) int32
    max_new_tokens: int
    eos_id: int | None
    t_submit: float
    quantize: bool = False  # int8 numeric path (engine must be armed)
    speculative: bool = False  # rides the verify block (engine must be armed)
    bucket: int | None = None
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    # Block diffusion (ISSUE 37): for each of ``tokens``, the denoise pass
    # of its block (0 .. denoise_steps - 1) that unmasked it. Empty under
    # one-token decode.
    token_passes: list[int] = dataclasses.field(default_factory=list)
    state: str = "queued"  # queued | running | done
    finish_reason: str | None = None
    # Serving observatory (ISSUE 13): the request's lifecycle trace
    # (phase dicts, mirrored as serve.trace events when tracing is
    # armed), its per-tick ITL observations (tick wall / tokens
    # committed — what the SLO gate and the access log read), the last
    # backpressure reason while queued, and its SLO violation count.
    trace: list[dict] = dataclasses.field(default_factory=list)
    itl_s: list[float] = dataclasses.field(default_factory=list)
    queue_reason: str | None = None
    slo_violations: int = 0
    drained: bool = False
    t_last_tick: float | None = None
    # End-to-end tracing (ISSUE 18): the propagated cross-process
    # TraceContext (obs.trace.TraceContext) when this request arrived
    # through the front door, else None — the untraced path stays one
    # `is not None` check.
    trace_ctx: Any = None
    # Disaggregated serving (ISSUE 19): a validated KVPageSet loaded at
    # submit (kv_key=...) — its pages restore at admission instead of
    # being recomputed; None rides the classic local-prefill path.
    kv_import: Any = None

    @property
    def done(self) -> bool:
        return self.state == "done"

    @property
    def group(self) -> str:
        """Traffic-group label: (fp|int8).(plain|spec) — the scheduler's
        decode-block partition, the split the SLO histograms report by."""
        return _ledger.group_key(self.quantize, self.speculative)

    @property
    def terminal_phase(self) -> str | None:
        """The trace's terminal phase (complete | drained), or None while
        the request is still in flight (or tracing is disarmed)."""
        for t in reversed(self.trace):
            if t.get("phase") in ("complete", "drained"):
                return t["phase"]
        return None

    @property
    def ttft_s(self) -> float | None:
        """Submit → first generated token (the prefill logits' argmax)."""
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def decode_tokens_per_s(self) -> float | None:
        """Post-first-token decode rate (the slot's steady-state share of
        the batched decode program)."""
        if self.t_done is None or self.t_first is None:
            return None
        n = len(self.tokens) - 1
        dur = self.t_done - self.t_first
        if n <= 0 or dur <= 0:
            return None
        return n / dur

    def result(self) -> np.ndarray:
        """Generated tokens so far (complete once ``done``)."""
        return np.asarray(self.tokens, np.int32)


class ServeEngine:
    """Request-level continuous-batching engine over one model.

    Greedy decoding only (the serving contract is token-exactness vs a
    solo ``generate(temperature=0)`` of the same prompt; stochastic
    per-request sampling would need per-slot rng plumbing that nothing
    consumes yet). Single-process: the cache lives on the default device
    set; on a sharded mesh the slot axis shards over 'data' through
    GSPMD exactly like the batch predictor's batches.
    """

    def __init__(
        self,
        model,
        params,
        *,
        max_slots: int | None = None,
        prefill_chunk: int | None = None,
        buckets=None,
        decode_block: int | None = None,
        pad_id: int = 0,
        quant: str | bool | None = None,
        paged: bool = True,
        page_size: int | None = None,
        n_pages: int | None = None,
        prefix_cache: bool | None = None,
        speculative: int | bool | None = None,
        spec_ngram: int = 3,
        role: str | None = None,
        kv_store_dir: str | None = None,
        kv_host_mb: float | None = None,
        kv_disk_dir: str | None = None,
        generation: dict | None = None,
    ):
        self.model = model
        self.params = params
        self.generation = resolve_generation(generation, model)
        # Cache leaves the model holds per slot, by name (``(..., max_slots
        # + 1, *state)`` in the engine's cache, the last row a trash row):
        # asked of the model, never guessed from shapes.
        self.slot_state = tuple(getattr(model, "slot_state", ()))
        # How the decode program advances that state, as the model says
        # (``state_step``: "inplace" or "xla"); None for a model without.
        self.state_step = (
            getattr(model, "state_step", lambda: None)() if self.slot_state else None
        )
        # Per-request int8 (ISSUE 9): quantize ONCE at construction and
        # keep both numeric paths' params resident — requests pick a
        # path at submit, never a recompile. The quantized tree is a
        # derived view of the same fp params (QuantLeaf pytrees), so
        # checkpoint reload/hot-swap stories stay single-source.
        self.quant_mode = resolve_serve_quant(quant)
        self._qmodel = self._qparams = None
        if self.quant_mode is not None:
            from tpuflow.infer.quant import QuantizedModel

            if isinstance(model, QuantizedModel):
                raise ValueError(
                    "ServeEngine(quant=...) wants the raw fp model/params "
                    "and owns both numeric paths; got an already-quantized "
                    "model — drop the wrapper or drop the quant arg"
                )
        self.n_ctx = int(model.config.n_ctx)
        self.max_slots = (
            int(max_slots)
            if max_slots is not None
            else _env_int("TPUFLOW_SERVE_SLOTS", 8)
        )
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if prefill_chunk is None:
            prefill_chunk = (
                _env_int("TPUFLOW_SERVE_PREFILL_CHUNK", 0, minimum=0) or None
            )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.prefill_chunk = prefill_chunk
        self.buckets = resolve_buckets(self.n_ctx, buckets)
        self.decode_block = (
            int(decode_block)
            if decode_block is not None
            else _env_int("TPUFLOW_SERVE_DECODE_BLOCK", 8)
        )
        if self.decode_block < 1:
            raise ValueError(
                f"decode_block must be >= 1, got {self.decode_block}"
            )
        # ``decode_block`` counts forward passes a program call. Under
        # one-token decode that is also the most tokens a row gains a
        # call; under block diffusion a block of L tokens takes S denoise
        # passes and a commit pass, a call runs whole blocks, and the
        # default is the blocks that yield about as many tokens a row as
        # the one-token default does.
        self._advance = self.decode_block  # positions a row can gain a call
        if self.generation is not None:
            L = self.generation["block_length"]
            per_block = self.generation["denoise_steps"] + 1
            if decode_block is None:
                self.decode_block = max(self.decode_block // L, 1) * per_block
            if self.decode_block % per_block:
                raise ValueError(
                    f"decode_block={self.decode_block} must be a multiple "
                    f"of denoise_steps + 1 = {per_block}: a call runs whole "
                    "blocks"
                )
            self._advance = self.decode_block // per_block * L
        self.pad_id = int(pad_id)
        # Serving observatory (ISSUE 13): lifecycle tracing, the
        # engine-time ledger (buckets sum to serve wall by
        # construction), declared SLOs, and the per-request access log.
        # All host-side — no jitted program gains an operand, so
        # compile_stats() is identical with everything armed.
        self._trace_on = _env_flag("TPUFLOW_SERVE_TRACE", True)
        self._access_on = _env_flag("TPUFLOW_SERVE_ACCESS_LOG", True)
        self._access: _ledger.AccessLog | None = None
        self.ledger = _ledger.ServeLedger(
            slo_ttft_s=_ledger.resolve_slo_s("TPUFLOW_SERVE_SLO_TTFT_MS"),
            slo_itl_s=_ledger.resolve_slo_s("TPUFLOW_SERVE_SLO_ITL_MS"),
        )
        # Device observatory (ISSUE 15): the anomaly-armed profiler
        # capturer (None unless TPUFLOW_PROF_TRIGGER — the disarmed
        # path is one `is not None` check per decode tick).
        self._profcap = _profcap.maybe_from_env()

        S = self.max_slots
        # Paged KV (ISSUE 11): the pool geometry + the per-slot page
        # tables. The decode model is the SAME module cloned with the
        # pool geometry in its config (params untouched) — geometry is
        # static by construction, tables are data.
        if not paged:  # the benchmark's files still pass paged=True
            raise ValueError(
                "ServeEngine(paged=False): the contiguous slot rows went "
                "with PR 32; the engine's cache is the page pool"
            )
        self.spec_draft = resolve_spec_draft(speculative)
        self.spec_ngram = int(spec_ngram)
        if self.spec_ngram < 2:
            raise ValueError(f"spec_ngram must be >= 2, got {spec_ngram}")
        # Disaggregated serving (ISSUE 19): the engine role, the
        # shared KV-page store (ship/import), and the tiered prefix
        # cache. Everything defaults off/"both" — an engine built with
        # no kv knobs is byte-identical to the classic one.
        self.role = resolve_serve_role(role)
        kv_dir = (
            kv_store_dir if kv_store_dir is not None
            else knobs.raw("TPUFLOW_KV_STORE_DIR")
        )
        self.kv_store = _kvstore.KVStore(kv_dir) if kv_dir else None
        self._tier: _kvstore.TierCache | None = None
        self._prefill_calls = 0
        self._row_tmpl = None
        self._qpmodel = None
        self.page_size = resolve_page_size(self.n_ctx, page_size)
        self.pages_per_slot = self.n_ctx // self.page_size
        # Tiered prefix cache: both tiers default OFF (the untiered
        # pool then works alone).
        host_mb = (
            float(kv_host_mb) if kv_host_mb is not None
            else float(knobs.get_float("TPUFLOW_KV_HOST_MB"))
        )
        tier_disk = (
            kv_disk_dir if kv_disk_dir is not None
            else knobs.raw("TPUFLOW_KV_DISK_DIR")
        )
        # What has no meaning yet beside a way of generating, or beside
        # state a request carries from token to token (a shipped, fed,
        # tiered or speculated admission skips or replays tokens that the
        # state has absorbed, or would have to): raised where the two
        # meet, never served in the option's place.
        holders = []
        if self.slot_state:
            holders.append(
                "a model that holds per-slot state "
                f"({', '.join(self.slot_state)})"
            )
        if self.generation is not None:
            holders.append(f"generation={self.generation['kind']!r}")
        for who in holders:
            for what, on in (
                ("quant (the int8 decode path)", self.quant_mode is not None),
                ("speculative decode", bool(self.spec_draft)),
                ("a KV store (shipped page sets)", self.kv_store is not None),
                ("a tiered prefix cache", host_mb > 0 or bool(tier_disk)),
                ("block-diffusion generation",
                 bool(self.slot_state) and self.generation is not None),
            ):
                if on:
                    raise GenerationUnsupported(
                        f"{who} with {what}: not supported yet"
                    )
        if self.generation is not None:
            if self.page_size % self.generation["block_length"]:
                # A page's contents then depend on tokens up to its own
                # end alone, which is what `PagePool.prefix_digests` keys
                # a shared page by; and a request's last block lies
                # inside the pages its prompt and budget already cover.
                raise ValueError(
                    f"page_size={self.page_size} must be a multiple of the "
                    f"generation's block_length="
                    f"{self.generation['block_length']}"
                )
        default_pages = S * self.pages_per_slot + 1
        self.n_pages = (
            int(n_pages) if n_pages is not None
            else _env_int("TPUFLOW_SERVE_PAGES", default_pages, minimum=2)
        )
        if self.n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the trash page), "
                f"got {self.n_pages}"
            )
        use_prefix = (
            _env_flag("TPUFLOW_SERVE_PREFIX_CACHE", True)
            if prefix_cache is None else bool(prefix_cache)
        )
        if use_prefix and (host_mb > 0 or tier_disk):
            self._tier = _kvstore.TierCache(
                host_bytes=int(host_mb * 2**20),
                disk_dir=tier_disk or None,
                index_max=int(knobs.get_int("TPUFLOW_KV_INDEX_MAX")),
                disk_max_bytes=int(
                    float(knobs.get_float("TPUFLOW_KV_DISK_MB"))
                    * 2**20
                ),
            )
        self._queue: collections.deque[ServeRequest] = collections.deque()
        self._slots: list[ServeRequest | None] = [None] * S
        # Under block diffusion a slot's current block: ids, -1 where the
        # position is still masked (the engine's own state: a prompt may
        # hold the mask id).
        self._tok = (
            np.zeros((S,), np.int32) if self.generation is None
            else np.full((S, self.generation["block_length"]), -1, np.int32)
        )
        self._lengths = np.zeros((S,), np.int32)
        self._pads = np.zeros((S,), np.int32)
        self._remaining = np.zeros((S,), np.int32)
        self._live = np.zeros((S,), bool)
        self._quant = np.zeros((S,), bool)  # slot rides the int8 path
        self._spec = np.zeros((S,), bool)  # slot rides the verify block
        self._eos = np.full((S,), -1, np.int32)
        self._next_id = 0
        self._iters = 0
        self._completed = 0
        self._emitted_tokens = 0
        self._spec_committed = 0
        self._spec_forwards = 0
        self._last_gauges: tuple | None = None
        # The engine's device work: the int8 weights, the pool and its
        # ladder, the cache.
        with obs.span(
            "serve.init", slots=S, pages=self.n_pages,
            quant=self.quant_mode or "off",
            **({"state_step": self.state_step} if self.state_step else {}),
        ):
            if self.quant_mode is not None:
                from tpuflow.infer.quant import quant_decision, quantize_model

                dec = quant_decision(params, mode=self.quant_mode)
                obs.event(
                    "quant.decision",
                    apply=True,  # per-request opt-in: forced, gate advisory
                    mode=dec.mode,
                    weight_mib=round(dec.weight_bytes / 2**20, 1),
                    reason="serve engine per-request int8 (submit(quantize=))",
                )
                self._qmodel, self._qparams = quantize_model(
                    model, params, mode=self.quant_mode
                )
            self.pool = PagePool(
                self.n_pages, self.page_size, prefix_cache=use_prefix,
                tier_cache=self._tier,
                page_reader=(
                    self._read_page_host if self._tier is not None else None
                ),
            )
            self._page_table = np.zeros((S, self.pages_per_slot), np.int32)
            self.decode_shapes = decode_ladder(S, self.n_ctx, self.page_size)
            self._slot_pages: list[list[int]] = [[] for _ in range(S)]
            rows = {"state_slots": S + 1} if self.slot_state else {}
            self._pmodel = model.clone(
                config=dataclasses.replace(
                    model.config,
                    kv_pages=self.n_pages,
                    kv_page_size=self.page_size,
                    **rows,
                )
            )
            self._cache = self._init_cache()

        self._prefill = jax.jit(
            functools.partial(self._prefill_fn, self.model),
            static_argnames=("chunk",),
        )
        self._insert = jax.jit(self._page_insert_fn, donate_argnums=(0,))
        self._decode = jax.jit(
            functools.partial(
                self._decode_fn if self.generation is None
                else self._denoise_fn,
                self._pmodel,
            ),
            donate_argnums=(1,),
        )
        self._verify = None
        if self.spec_draft:
            self._verify = jax.jit(
                functools.partial(self._verify_fn, self._pmodel),
                donate_argnums=(1,),
            )
        self._prefill_q = self._decode_q = self._verify_q = None
        if self.quant_mode is not None:
            # The int8 twins: same program SHAPES (slot arrays, cache
            # pytree, bucket widths), different static model + params
            # pytree — so fp and int8 requests interleave through one
            # engine with zero fresh compiles after warmup.
            # The int8 wrapper around the PAGED clone for the decode
            # programs (the prefill twin keeps the row-cache model).
            self._qpmodel = dataclasses.replace(
                self._qmodel, model=self._pmodel
            )
            self._prefill_q = jax.jit(
                functools.partial(self._prefill_fn, self._qmodel),
                static_argnames=("chunk",),
            )
            self._decode_q = jax.jit(
                functools.partial(self._decode_fn, self._qpmodel),
                donate_argnums=(1,),
            )
            if self.spec_draft:
                self._verify_q = jax.jit(
                    functools.partial(self._verify_fn, self._qpmodel),
                    donate_argnums=(1,),
                )

    # ------------------------------------------------------- jitted programs
    def _init_cache(self):
        """Zeroed KV cache with the decode model's exact cache pytree
        (eval_shape — no compile, no garbage forward): the pool, each
        leaf ``(..., n_pages, page_size, width)`` with ``width`` what a
        token holds, flattened and padded to whole 128-lane rows
        (``ops/paged_pool.py``: the chip lays a leaf out page-major, as
        every program here indexes it, only when its minor axis fills
        whole 128-lane rows). Also, per leaf (by ``keystr`` of its path,
        which a prefill row's leaves share), ``self._page_axis``: the
        axis that counts pages in the pool and is the slot axis of a row
        ``(..., 1, n_ctx, *token)``, None for a leaf the pool does not
        hold by pages (the index scalars); and ``self._token_shape``:
        what a token holds there as a row shapes it (the page set's
        format). Both are asked of the model, never guessed from sizes:
        the page axis is the first one in which the model's pool and the
        model's prefill row differ. The ledger is told once how many
        numbers a token holds and what they are padded to
        (``serve.pool_pad_fraction``).

        A leaf the model names in ``slot_state`` is held per slot, not
        by pages: ``self._slot_axis`` keeps its slot axis (again the
        first in which pool and row differ: ``max_slots + 1`` rows, the
        last a trash row, against the prefill row's one), the admission
        insert writes the prefill's final state into the slot's row, and
        the decode program reads and writes the rows of its live slots
        by index (``serve.slot_state_bytes`` says what they hold,
        ``serve.state_step_inplace`` whether it advances them where they
        lie)."""

        def mk(params):
            _, variables = self._pmodel.apply(
                {"params": params},
                jnp.zeros((self.max_slots, 1), jnp.int32),
                decode=True,
                mutable=["cache"],
                slot_index=jnp.zeros((self.max_slots,), jnp.int32),
                page_table=jnp.zeros(
                    (self.max_slots, self.pages_per_slot), jnp.int32
                ),
            )
            return variables["cache"]

        shapes = jax.eval_shape(mk, self.params)
        self._page_axis, self._token_shape, widths = {}, {}, {}
        self._slot_axis: dict[str, int] = {}
        held = {f"[{name!r}]" for name in self.slot_state}
        state_bytes = 0
        for (path, pool), row in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_leaves(self._row_template()),
        ):
            key = jax.tree_util.keystr(path)
            axis = next(
                (i for i, (a, b) in enumerate(zip(pool.shape, row.shape))
                 if a != b),
                None,
            )
            if key in held:
                if axis is None or pool.shape[axis] != self.max_slots + 1:
                    raise ValueError(
                        f"slot state {key} {pool.shape}: a per-slot leaf is "
                        f"(..., max_slots + 1, *state), the prefill row's "
                        f"{row.shape} with one row"
                    )
                self._slot_axis[key] = axis
                held.discard(key)
                state_bytes += pool.size * pool.dtype.itemsize
                axis = None  # not a page pool
            self._page_axis[key] = axis
            if axis is None:
                continue
            if axis != pool.ndim - 3 or pool.shape[axis] != self.n_pages:
                raise ValueError(
                    f"cache leaf {key} {pool.shape}: a pool leaf is (..., "
                    "kv_pages, kv_page_size, width), one vector a token "
                    "(ops/paged_pool.py)"
                )
            self._token_shape[key] = row.shape[axis + 2:]
            widths[key] = (math.prod(row.shape[axis + 2:]), pool.shape[-1])
        if held:
            raise ValueError(f"the model's slot state {sorted(held)} is not in its cache")
        self.ledger.pool_token_widths = widths
        obs.gauge(
            "serve.pool_pad_fraction", round(self.ledger.pool_pad_fraction, 4)
        )
        if self.slot_state:
            self.ledger.slot_state_bytes = state_bytes
            obs.gauge("serve.slot_state_bytes", state_bytes)
        if self.state_step is not None:
            self.ledger.state_step_inplace = int(self.state_step == "inplace")
            obs.gauge("serve.state_step_inplace", self.ledger.state_step_inplace)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )

    @jax.named_scope("serve.prefill")
    def _prefill_fn(self, model, params, prompt, pads, *, chunk):
        """(1, W) admission prefill → (first greedy token (1,), cache row).
        One program per bucket width W (chunk is fixed per engine);
        ``model`` is partial-bound per numeric path (fp / int8)."""
        logits, cache = chunked_prefill(
            model, params, prompt, chunk, pad_lens=pads
        )
        with jax.named_scope("sample"):
            tok0 = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return tok0, cache

    @jax.named_scope("serve.insert")
    def _page_insert_fn(self, cache, row_cache, table_row, pad, write_mask,
                        slot=None):
        """The admission insert: strip the (1, n_ctx) prefill row's
        LEFT padding (roll by ``pad`` — the real prompt kv moves to
        logical columns [0, len), making cache content pad-invariant,
        the property prefix sharing rests on) and scatter its logical
        pages into the pool slots ``table_row`` names. ``write_mask``
        guards each page: shared prefix pages and unneeded tail entries
        are masked OFF — their writes route to the trash page — so a
        refcounted page is never rewritten by a matching admission.
        All three controls are DATA (no recompile per admission).

        The pool is updated in place by index, as the decode program
        does it (``Block._paged_attention``): a leaf
        ``(..., n_pages, page_size, width)`` — one block's pool or the
        layer-stacked one; ``width`` is what a token holds (K or V of
        (H, D), a latent vector) flattened and padded to whole 128-lane
        rows, the shape the chip keeps page-major — is flattened over
        its leading axes to ``(layers * n_pages, page_size, width)`` and
        takes ONE scatter of the row's ``layers * pages_per_slot`` pages
        at ``layer * n_pages + page``. The row ``(..., 1, n_ctx,
        *token)`` is flattened (and zero-padded, where the pool is) to
        that width first: row-sized work. Nothing is read back from the
        pool and no other page is touched.

        ``slot`` (a model with slot state): the row of the per-slot
        leaves that takes the prefill's final state, whole, on every
        admission, a prefix-cache hit among them (the prefill still runs
        over the whole prompt, so that state is fresh): a reused slot
        never starts from its last occupant's."""
        idx = jnp.where(write_mask, table_row, 0)

        def put(path, pool, row):
            key = jax.tree_util.keystr(path)
            if key in self._slot_axis:
                return jax.lax.dynamic_update_slice_in_dim(
                    pool, row.astype(pool.dtype), slot, self._slot_axis[key]
                )
            if self._page_axis[key] is None:
                return pool  # scalar index leaves pass through
            tail = pool.shape[-2:]  # (page_size, width)
            layers = math.prod(pool.shape[:-3])
            rows = jnp.roll(
                row.reshape(layers, self.n_ctx, -1), -pad, axis=1
            ).astype(pool.dtype)
            pages = _pool.pad_lanes(rows, tail[1]).reshape(
                (-1,) + tail
            )  # (layers * pages_per_slot, page_size, width)
            first_page = jnp.arange(layers) * self.n_pages
            at = (first_page[:, None] + idx[None, :]).reshape(-1)
            return pool.reshape((-1,) + tail).at[at].set(pages).reshape(
                pool.shape
            )

        return jax.tree_util.tree_map_with_path(put, cache, row_cache)

    @jax.named_scope("serve.verify")
    def _verify_fn(self, model, params, cache, page_table, tok, draft,
                   lengths, pads, remaining, live, eos):
        """The speculative verify block: ONE
        (S, draft_len + 1) forward over [cur, draft...] per slot, then a
        PER-ROW commit — the accepted draft prefix plus the model's
        bonus token at the first disagreement, truncated by each row's
        eos / budget / capacity. Rows advance independently (the paged
        cache has no shared index to rewind; rejected-tail kv beyond a
        row's new frontier is masked until its own next forward
        overwrites it — the solo ladder's rewind argument, per row).
        Acceptance compares argmaxes of this one forward, width-safe
        under decode_precision='highest' (and exactly under int8's
        integer contractions), so committed tokens are bit-equal to
        single-token greedy decode. Returns
        (cache, emitted (S, K+1), tok, lengths, remaining, live)."""
        K = self.spec_draft
        n_ctx = self.n_ctx
        pad_id = self.pad_id
        S = tok.shape[0]
        x = jnp.concatenate([tok[:, None], draft], axis=1)  # (S, K+1)
        logits, variables = model.apply(
            {"params": params, "cache": cache},
            x,
            decode=True,
            mutable=["cache"],
            pad_lens=pads,
            slot_index=lengths,
            page_table=page_table,
        )
        cache = variables["cache"]
        with jax.named_scope("sample"):
            am = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, K+1)
        # am[:, j] = the model's token after (cur, d_0..d_{j-1});
        # acceptance = leading agreement with the draft, as in the solo
        # ladder — but applied PER ROW.
        match = am[:, :K] == draft
        a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        j = jnp.arange(K + 1)
        # Committed window w[0..a] = accepted drafts then the bonus
        # token; entries past a are junk a masked commit never reads.
        w = jnp.where(
            j[None, :] < a[:, None],
            jnp.pad(draft, ((0, 0), (0, 1))),
            am[jnp.arange(S)[:, None], jnp.minimum(j[None, :], a[:, None])],
        )
        # Per-row commit count: acceptance + bonus, capped by budget and
        # capacity (live rows hold remaining >= 1 and lengths < n_ctx,
        # so c >= 1 — every verify makes progress, no livelock).
        c = jnp.minimum(jnp.minimum(a + 1, remaining), n_ctx - lengths)
        # eos truncation: commit up to and INCLUDING the first eos in
        # the window (generate()'s eos-is-emitted contract), then die.
        is_eos = w == eos[:, None]  # eos == -1 never matches real tokens
        first_eos = jnp.argmax(is_eos, axis=1)  # 0 when none (guarded)
        has_eos = jnp.any(is_eos & (j[None, :] < c[:, None]), axis=1)
        c = jnp.where(has_eos, jnp.minimum(c, first_eos + 1), c)
        c = jnp.where(live, c, 0)
        emitted = jnp.where(j[None, :] < c[:, None], w, pad_id)
        new_tok = w[jnp.arange(S), jnp.maximum(c - 1, 0)]
        tok = jnp.where(c > 0, new_tok, tok)
        lengths = lengths + c
        remaining = remaining - c
        live = live & ~has_eos & (remaining > 0) & (lengths < n_ctx)
        # Same carry layout as the decode block: the scheduler merges and
        # harvests both programs through one code path (tokens-per-row =
        # the remaining-budget delta, which c already decremented).
        return cache, emitted, tok, lengths, remaining, live

    @jax.named_scope("serve.decode")
    def _decode_fn(self, model, params, cache, tok, lengths, pads,
                   remaining, live, eos, page_table, slots=None):
        """THE persistent decode program: ``decode_block`` single-token
        steps over the R rows it is given, per-row freezing inside the
        scan. One host sync per block. The operands are (R,) arrays and
        an (R, W) ``page_table`` (loop-invariant data, into every step):
        the scheduler hands it the group's live slots, padded with dead
        ones to a shape of ``decode_ladder``, and the table columns that
        hold their frontier, so each (R, W) is one entry of this jit's
        cache and the read is R x W pages a layer. Dead rows keep
        rewriting one cache column with pad-token k/v — routed to the
        trash page by their zeroed tables, as is any write beyond the
        table's width. ``model`` is partial-bound per numeric path: the
        int8 twin runs the same program shapes with the fused-native
        W8A8 matmuls.

        What the model sows of a step into its ``step_sum`` and
        ``step_max`` collections (scalars: the experts a routed layer
        touched, say; GPT-2 sows nothing) comes back as the last result,
        summed and taken the largest of over the block's steps.

        ``slots`` (R,), a model with slot state: the slot of each row.
        A step hands the model its live rows' slots and the trash row
        (``max_slots``) for every other row, so a dead or padding row
        never touches a live slot's state; the state leaves are carried
        through both scans and updated by index."""
        n_ctx = self.n_ctx
        pad_id = self.pad_id

        def one(carry, _):
            cache, tok, lengths, remaining, live = carry
            rows = {} if slots is None else {
                "state_slots": jnp.where(live, slots, self.max_slots)
            }
            logits, variables = model.apply(
                {"params": params, "cache": cache},
                tok[:, None],
                decode=True,
                mutable=["cache", "step_sum", "step_max"],
                pad_lens=pads,
                slot_index=lengths,
                page_table=page_table,
                **rows,
            )
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            emitted = jnp.where(live, nxt, pad_id)
            lengths = jnp.where(live, lengths + 1, lengths)
            remaining = jnp.where(live, remaining - 1, remaining)
            # eos itself IS emitted (generate()'s contract); the slot
            # freezes after it. `lengths < n_ctx` guards the NEXT write.
            live = (
                live
                & (nxt != eos)
                & (remaining > 0)
                & (lengths < n_ctx)
            )
            sown = (
                dict(variables.get("step_sum", {})),
                dict(variables.get("step_max", {})),
            )
            return (
                variables["cache"], emitted, lengths, remaining, live
            ), (emitted, sown)

        (cache, tok, lengths, remaining, live), (toks, sown) = jax.lax.scan(
            one,
            (cache, tok, lengths, remaining, live),
            None,
            length=self.decode_block,
        )
        steps = (
            jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), sown[0]),
            jax.tree_util.tree_map(lambda a: jnp.max(a, axis=0), sown[1]),
        )
        return cache, toks.T, tok, lengths, remaining, live, steps

    @jax.named_scope("serve.decode")
    def _denoise_fn(self, model, params, cache, tok, lengths, pads,
                    remaining, live, eos, page_table):
        """The decode program under ``generation`` (block diffusion): a
        scan over whole blocks, ``decode_block`` forward passes in all,
        with the operands of ``_decode_fn`` except that ``tok`` (R, L)
        holds each row's current block, -1 where a position is masked.
        ``lengths`` is the block's first column, a multiple of L: every
        row is at a block's start when a call begins and ends.

        A block is S denoise passes and one commit pass, every row in
        step. A pass runs the model over the rows' whole blocks (a masked
        position's input is ``mask_id``), which writes the block's keys and
        values at its own columns and attends every earlier column and the
        block itself, in both directions. A denoise pass then unmasks, in
        each live row, up to L / S of the *eligible* positions, the masked
        ones inside the row's budget, each with the argmax at its
        position: ``sequential`` takes the leftmost, ``low_confidence``
        those whose largest softmax probability is highest. A row whose
        budget ends inside a block dies there: the block's other positions
        are never emitted and the block is never committed. The commit
        pass runs the finished block once more and computes no head: a
        position's keys and values depend on the other tokens of its
        block, so what a denoise pass wrote was of a block still partly
        masked, and what this pass writes is what later blocks read.

        Returns ``_decode_fn``'s tuple and one more: the call's new tokens
        a row, left-aligned in position order (R, blocks x L), the carries,
        the model's sown counts over all passes, and for each new token
        the denoise pass (0 .. S - 1) that unmasked it. Tokens a row is
        the ``remaining`` delta, as for the verify block."""
        gen = self.generation
        L, S = gen["block_length"], gen["denoise_steps"]
        per_pass = L // S
        n_ctx, pad_id = self.n_ctx, self.pad_id
        del eos  # submit() refuses eos_id under generation

        def forward(cache, tok, lengths, head):
            logits, variables = model.apply(
                {"params": params, "cache": cache},
                jnp.where(tok < 0, gen["mask_id"], tok),
                decode=True,
                mutable=["cache", "step_sum", "step_max"],
                pad_lens=pads,
                slot_index=lengths,
                page_table=page_table,
                head=head,
            )
            sown = (
                dict(variables.get("step_sum", {})),
                dict(variables.get("step_max", {})),
            )
            return logits, variables["cache"], sown

        def unmask(logits, tok, when, remaining, live, s):
            best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            masked = tok < 0
            eligible = (
                masked
                & (jnp.cumsum(masked, axis=1) <= remaining[:, None])
                & live[:, None]
            )
            if gen["unmask"] == "sequential":
                chosen = eligible & (jnp.cumsum(eligible, axis=1) <= per_pass)
            else:
                confidence = jnp.exp(
                    jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)
                )
                _, at = jax.lax.top_k(
                    jnp.where(eligible, confidence, -1.0), per_pass
                )
                chosen = eligible & jnp.any(
                    jnp.arange(L)[None, None, :] == at[:, :, None], axis=1
                )
            remaining = remaining - jnp.sum(chosen, axis=1)
            return (
                jnp.where(chosen, best, tok), jnp.where(chosen, s, when),
                remaining, live & (remaining > 0),
            )

        def block(carry, _):
            cache, tok, lengths, remaining, live = carry
            given = tok >= 0  # a first block's prompt tokens

            def denoise(carry, s):
                cache, *state = carry
                logits, cache, sown = forward(cache, state[0], lengths, True)
                with jax.named_scope("unmask"):
                    state = unmask(logits, *state, s)
                return (cache, *state), sown

            with jax.named_scope("denoise"):
                (cache, tok, when, remaining, live), sown = jax.lax.scan(
                    denoise,
                    (cache, tok, jnp.full(tok.shape, -1, jnp.int32),
                     remaining, live),
                    jnp.arange(S),
                )
            with jax.named_scope("denoise.commit"):
                _, cache, committed = forward(cache, tok, lengths, False)
            new = (tok >= 0) & ~given
            # A live row's block is full (its budget never bound): it
            # moves on to an all-masked block, if one fits.
            lengths = jnp.where(live, lengths + L, lengths)
            out = (jnp.where(new, tok, pad_id), jnp.where(new, when, -1), new)
            tok = jnp.where(live[:, None], -1, tok)
            live = live & (lengths + L <= n_ctx)
            sown = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b[None]]), sown, committed
            )
            return (cache, tok, lengths, remaining, live), (out, sown)

        carry, ((toks, when, new), sown) = jax.lax.scan(
            block,
            (cache, tok, lengths, remaining, live),
            None,
            length=self.decode_block // (S + 1),
        )
        cache, tok, lengths, remaining, live = carry
        steps = (
            jax.tree_util.tree_map(jnp.sum, sown[0]),
            jax.tree_util.tree_map(jnp.max, sown[1]),
        )
        # (blocks, R, L) -> (R, blocks x L), the new tokens first, in
        # position order.
        flat = lambda a: jnp.swapaxes(a, 0, 1).reshape(a.shape[1], -1)  # noqa: E731
        order = jnp.argsort(~flat(new), axis=1, stable=True)
        toks, when = (
            jnp.take_along_axis(flat(a), order, axis=1) for a in (toks, when)
        )
        return cache, toks, tok, lengths, remaining, live, steps, when

    # ------------------------------------------------------------ scheduling
    def bucket_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """Smallest bucket width holding the prompt, where the prompt and
        its budget fit n_ctx: the check is on the REAL prompt length (the
        page insert strips bucket pads, so pads cost prefill FLOPs only,
        never cache columns)."""
        if prompt_len + max_new_tokens <= self.n_ctx:
            for w in self.buckets:
                if prompt_len <= w:
                    return w
        raise ValueError(
            f"no prefill bucket fits prompt_len={prompt_len} + "
            f"max_new_tokens={max_new_tokens} within n_ctx={self.n_ctx} "
            f"(buckets: {self.buckets})"
        )

    def _pages_needed(self, req: ServeRequest) -> int:
        """Pages covering every logical column the request's programs
        can touch: prompt + budget, plus the verify block's draft-length
        overshoot slack for speculative requests (rejected-tail writes
        land in-bounds; >= n_ctx routes to trash)."""
        slack = self.spec_draft if req.speculative else 0
        top = min(self.n_ctx, req.prompt.size + req.max_new_tokens + slack)
        return -(-top // self.page_size)

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int,
        eos_id: int | None = None,
        quantize: bool = False,
        speculative: bool | None = None,
        trace: Any = None,
        kv_key: str | None = None,
    ) -> ServeRequest:
        """Enqueue one request; returns its live handle. Validation is
        eager (a request that can never fit must fail at submit, not
        half-way through a decode block). ``quantize=True`` routes the
        request through the engine's int8 programs (requires a
        quant-armed engine: ``quant=`` / ``TPUFLOW_SERVE_QUANT``).
        ``speculative`` routes it through the verify block on a
        spec-armed engine (None = the engine default: on when armed);
        ``speculative=True`` on an unarmed engine raises — the verify
        programs compile at warmup, never mid-flight. ``kv_key`` names a
        shipped page set in the engine's KV store (ISSUE 19): a loadable
        matching set admits the request already-prefilled; a missing /
        torn / mismatched one degrades to local prefill (``kv_fallback``
        trace), never an error."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if self.generation is not None and eos_id is not None:
            raise GenerationUnsupported(
                f"generation={self.generation['kind']!r} with eos_id: a "
                "block is unmasked out of order, so where a request ends "
                "is its budget's to say (not supported yet)"
            )
        if quantize and self.quant_mode is None:
            raise ValueError(
                "submit(quantize=True) needs a quant-armed engine: pass "
                "ServeEngine(quant='fused_native') or set "
                "TPUFLOW_SERVE_QUANT=1 (the int8 programs compile at "
                "warmup, never mid-flight)"
            )
        if speculative and not self.spec_draft:
            raise ValueError(
                "submit(speculative=True) needs a spec-armed engine: "
                "pass ServeEngine(speculative=K) or set "
                "TPUFLOW_SERVE_SPEC=K (the verify programs compile at "
                "warmup, never mid-flight)"
            )
        spec = bool(self.spec_draft) if speculative is None else bool(
            speculative
        )
        kv_import = None
        if kv_key is not None and self.kv_store is not None:
            with obs.span("serve.kv_import", key=kv_key) as sp:
                pset = self.kv_store.load(kv_key)
                if pset is not None and self._import_ok(
                    pset, prompt, quantize
                ):
                    kv_import = pset
                sp.set(
                    ok=kv_import is not None,
                    pages=0 if pset is None else pset.n_pages,
                )
        bucket = self.bucket_for(prompt.size, max_new_tokens)
        req = ServeRequest(
            id=self._next_id,
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            eos_id=None if eos_id is None else int(eos_id),
            t_submit=time.monotonic(),
            quantize=bool(quantize),
            speculative=spec,
            bucket=bucket,
            trace_ctx=trace,
        )
        if self._pages_needed(req) > self.pool.usable_pages:
            raise ValueError(
                f"request needs {self._pages_needed(req)} pages but the "
                f"pool holds {self.pool.usable_pages} usable pages "
                f"(n_pages={self.n_pages}, page_size={self.page_size}) — "
                "it could never admit; raise TPUFLOW_SERVE_PAGES"
            )
        req.kv_import = kv_import
        self._next_id += 1
        self._queue.append(req)
        self._trace(
            req, "submitted", prompt_len=int(prompt.size),
            max_new=req.max_new_tokens, bucket=bucket, group=req.group,
        )
        if kv_key is not None and kv_import is None:
            # Local-prefill fallback: the shipped set was missing, torn,
            # or mismatched — the request proceeds as if never shipped.
            self._trace(req, "kv_fallback", key=kv_key)
        return req

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def live_slots(self) -> int:
        return int(self._live.sum())

    def compile_stats(self) -> dict[str, int]:
        """Jit-cache sizes of the engine's programs (including the int8
        twins on a quant-armed engine and the speculative verify blocks
        on a spec-armed one). After ``warmup()`` these must never grow —
        the never-recompile contract, pinned by tests/test_serve.py."""
        stats = {
            "prefill": int(self._prefill._cache_size()),
            "insert": int(self._insert._cache_size()),
            "decode": int(self._decode._cache_size()),
        }
        if self.spec_draft:
            stats["verify"] = int(self._verify._cache_size())
        if self.quant_mode is not None:
            stats["prefill_q"] = int(self._prefill_q._cache_size())
            stats["decode_q"] = int(self._decode_q._cache_size())
            if self.spec_draft:
                stats["verify_q"] = int(self._verify_q._cache_size())
        return stats

    def residency_efficiency(self) -> float | None:
        """HBM residency: tokens resident (live slots' committed cache
        columns) / tokens allocated (live slots' held pages x page_size):
        a short request strands only the tail of its last page. None when
        idle."""
        live = np.nonzero(self._live)[0]
        if live.size == 0:
            return None
        resident = int((self._lengths[live] - self._pads[live]).sum())
        allocated = sum(
            len(self._slot_pages[int(s)]) for s in live
        ) * self.page_size
        if allocated <= 0:
            return None
        return resident / allocated

    # ------------------------------------- disaggregated serving (ISSUE 19)
    def _cache_leaf_items(self, tree):
        """``(path-key, leaf)`` for every leaf of ``tree`` (the pool, or
        a prefill row of the same structure) that the pool holds by
        pages, in canonical flatten order — the shared leaf naming that
        page bundles, shipped sets, and the tier store all key on.
        ``self._page_axis[key]`` is the page axis of the pool leaf and
        the slot axis of the row leaf."""
        out = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = jax.tree_util.keystr(path)
            if self._page_axis.get(key) is not None:
                out.append((key, leaf))
        return out

    def _read_page_host(self, pid: int) -> dict[str, np.ndarray]:
        """Pool page ``pid`` as a host-side per-leaf bundle ``(...,
        page_size, *token)`` — the spill/promotion unit, in the page
        set's format (a prefill row's token shape: the pool's flattened,
        padded vector is reshaped and stripped here, on one page). Eager
        gathers: no named program, so ``compile_stats()`` never sees
        this."""
        out = {}
        for key, leaf in self._cache_leaf_items(self._cache):
            page = np.asarray(jnp.take(leaf, pid, axis=self._page_axis[key]))
            out[key] = _pool.strip_lanes(page, self._token_shape[key])
        return out

    def _row_template(self):
        """Shape/dtype pytree of a prefill cache row via
        ``jax.eval_shape`` (no compile, no device work), cached. Row
        leaves are bucket-independent — ``(..., 1, n_ctx, *token)`` plus
        the row model's index scalars — so one template serves every
        restore."""
        if self._row_tmpl is None:
            W = self.buckets[0]
            pads = prompt_lens_to_pad_lens([1], 1, W)
            chunk = normalize_prefill_chunk(self.prefill_chunk, W)
            self._row_tmpl = jax.eval_shape(
                functools.partial(
                    self._prefill_fn, self.model, chunk=chunk
                ),
                self.params, jnp.zeros((1, W), jnp.int32), pads,
            )[1]
        return self._row_tmpl

    def _synth_row(self, pages: dict[int, dict[str, np.ndarray]]):
        """A zeroed prefill-row pytree with ``pages`` (logical page
        index -> bundle) written at their columns. Moulded on the
        :meth:`_row_template` shapes/dtypes — the EXACT signature of a
        real prefill row — so the warmed ``_insert`` scatters it with
        ``pad=0`` and zero fresh compiles (pinned by
        tests/test_serve_disagg.py). Index scalars are zeroed host
        arrays: the insert passes them through unread, and a fresh
        buffer never aliases the donated cache operand."""
        ps = self.page_size
        tmpl = self._row_template()
        rows = jax.tree_util.tree_map(
            lambda leaf: np.zeros(leaf.shape, leaf.dtype), tmpl
        )
        flat = dict(
            (jax.tree_util.keystr(path), row)
            for path, row in jax.tree_util.tree_flatten_with_path(rows)[0]
        )
        for key, _ in self._cache_leaf_items(tmpl):
            lead = (slice(None),) * self._page_axis[key]
            for j, bundle in pages.items():
                page = bundle.get(key)
                if page is not None:
                    flat[key][lead + (0, slice(j * ps, (j + 1) * ps))] = page
        return rows

    def _restore_pages(
        self, table_row: np.ndarray, pages: dict[int, dict], request: int
    ) -> None:
        """Scatter restored page bundles (tier promotions / shipped
        pages) into the pool slots ``table_row`` names — one masked
        ``_insert`` over a synthesized row, the admission insert's exact
        program signature."""
        if not pages:
            return
        write_mask = np.zeros((self.pages_per_slot,), bool)
        for j in pages:
            write_mask[j] = True
        # Device-resident leaves on purpose: the jit cache distinguishes
        # committed arrays (what the warmed insert saw — prefill output)
        # from host numpy operands, and a distinct entry would break the
        # never-recompile contract.
        row = jax.tree_util.tree_map(jnp.asarray, self._synth_row(pages))
        with self.ledger.bucket("insert"), obs.span(
            "serve.insert", request=request, restored=len(pages)
        ):
            self._cache = self._insert(
                self._cache, row, jnp.asarray(table_row),
                jnp.int32(0), jnp.asarray(write_mask),
                *self._slot_operand(self.max_slots),
            )

    def prefill_export(
        self, prompt, *, quantize: bool = False
    ) -> _kvstore.KVPageSet:
        """Run admission prefill for ``prompt`` and extract its KV pages
        as a :class:`~tpuflow.infer.kv_store.KVPageSet` — the
        prefill-role half of a disaggregated pair. The row comes from
        the SAME bucketed prefill program an admission uses, then is
        pad-stripped host-side (np.roll by ``-(W - L)``), so page
        content is bit-equal to what a local admission would have
        inserted (PR 11's pad-invariance). Includes the partial tail
        page (private to the request — decode writes land there) and
        the first greedy token, so an exact import admits with zero
        prefill."""
        if self.generation is not None:
            raise GenerationUnsupported(
                f"generation={self.generation['kind']!r} with "
                "prefill_export / ship: a shipped set carries a first "
                "token and whole-prompt pages, which a block-wise "
                "admission has neither of (not supported yet)"
            )
        if self.slot_state:
            raise GenerationUnsupported(
                "a model that holds per-slot state with prefill_export / "
                "ship: a page set carries no state (not supported yet)"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must have at least one token")
        L = int(prompt.size)
        W = self.bucket_for(L, 1)
        padded = np.full((1, W), self.pad_id, np.int32)
        padded[0, W - L:] = prompt
        pads = prompt_lens_to_pad_lens([L], 1, W)
        chunk = normalize_prefill_chunk(self.prefill_chunk, W)
        prefill = self._prefill_q if quantize else self._prefill
        prm = self._qparams if quantize else self.params
        self._prefill_calls += 1
        with self.ledger.bucket("prefill"):
            tok0, row_cache = prefill(
                prm, jnp.asarray(padded), pads, chunk=chunk
            )
            first = int(np.asarray(tok0)[0])
        ps = self.page_size
        k_ship = -(-L // ps)
        pages: dict[str, np.ndarray] = {}
        for key, leaf in self._cache_leaf_items(row_cache):
            axis = self._page_axis[key]
            row = np.asarray(leaf)  # (..., 1, n_ctx, *token)
            sq = np.take(np.roll(row, -(W - L), axis=axis + 1), 0, axis=axis)
            paged = sq.reshape(
                sq.shape[:axis] + (self.pages_per_slot, ps)
                + sq.shape[axis + 1:]
            )
            paged = np.moveaxis(paged, axis, 0)
            pages[key] = np.ascontiguousarray(paged[:k_ship])
        return _kvstore.KVPageSet(
            page_size=ps,
            n_tokens=L,
            prompt=prompt,
            digests=_kvstore.chain_digests(prompt, ps),
            pages=pages,
            tok0=first,
            meta={"quant": bool(quantize)},
        )

    def ship(self, prompt, *, quantize: bool = False, store=None) -> str:
        """Prefill + commit: the prefill-role request path. Returns the
        committed ``kv_key`` the router forwards to a decode replica
        (``submit(..., kv_key=...)``)."""
        st = store if store is not None else self.kv_store
        if st is None:
            raise ValueError(
                "ship() needs a KV store: pass store= or set "
                "TPUFLOW_KV_STORE_DIR"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with obs.span(
            "serve.kv_ship", prompt_len=int(prompt.size),
            quant=bool(quantize),
        ) as sp:
            pset = self.prefill_export(prompt, quantize=quantize)
            key = st.commit(pset)
            sp.set(key=key, pages=pset.n_pages)
        return key

    def _import_ok(self, pset, prompt, quantize: bool) -> bool:
        """A shipped set is usable when its geometry and numeric path
        match and it covers this prompt — exactly (full ship: zero
        prefill) or as a digest-chain prefix (suffix resume: import the
        covered pages, prefill only the suffix). Anything else rides
        local prefill; the serving path never raises on a bad set."""
        if pset.page_size != self.page_size or not pset.pages:
            return False
        if bool(pset.meta.get("quant")) != bool(quantize):
            return False
        if pset.n_tokens == prompt.size and np.array_equal(
            np.asarray(pset.prompt, np.int32), prompt
        ):
            return True
        mine = _kvstore.chain_digests(prompt, self.page_size)
        return _kvstore.chain_match(pset.digests, mine) > 0

    def _note_first_token(self, req: ServeRequest, now: float) -> None:
        """TTFT bookkeeping — shared by the classic admission path and
        the prefill-free ones (full ship / decode-feed, where the first
        token lands in a decode harvest): same gauge, lifecycle trace,
        SLO gate, and goodput note either way."""
        req.t_first = now
        obs.gauge("serve.ttft_s", round(req.ttft_s, 6))
        # On the spans' clock and with the request's id, whether or not
        # the lifecycle traces are armed: with serve.complete it bounds
        # the interval in which the request's tokens come out.
        obs.event("serve.first_token", request=req.id, mono=now)
        self._trace(req, "first_token", ttft_s=round(req.ttft_s, 6))
        self.ledger.note_ttft(req.group, req.ttft_s)
        if self.ledger.check_ttft(req.ttft_s, group=req.group):
            self._slo_violation(
                req, "ttft", req.ttft_s, self.ledger.slo_ttft_s
            )
        ctx = req.trace_ctx
        obs.goodput_live().note_serve_ttft(
            req.ttft_s,
            trace_id=(
                ctx.trace_id
                if ctx is not None and ctx.recorded else None
            ),
        )

    # ------------------------------------------- lifecycle traces (ISSUE 13)
    def _trace(self, req: ServeRequest, phase: str, **attrs) -> None:
        """One lifecycle transition: appended to the request's host-side
        trace and mirrored as a serve.trace event. One bool check when
        disarmed (TPUFLOW_SERVE_TRACE=0) — pinned by the overhead test."""
        if not self._trace_on:
            return
        if req.trace_ctx is not None:
            # End-to-end tracing (ISSUE 18): lifecycle events carry the
            # propagated trace id; without a front-door context the key
            # is absent (never an empty string) — pinned by tests.
            attrs["trace_id"] = req.trace_ctx.trace_id
        req.trace.append({"phase": phase, "t": time.monotonic(), **attrs})
        obs.event("serve.trace", request=req.id, phase=phase, **attrs)

    def _tid(self, req: ServeRequest) -> dict:
        """``{"trace_id": ...}`` when a propagated context rides the
        request, else ``{}`` — spread into serve.* lifecycle events so
        the untraced shape is byte-identical to pre-trace builds."""
        ctx = req.trace_ctx
        return {} if ctx is None else {"trace_id": ctx.trace_id}

    def _note_queued(self, req: ServeRequest, reason: str) -> None:
        """Backpressure evidence: trace the queued phase once per reason
        change (a request waiting 10k iterations on a full pool must not
        write 10k events)."""
        if req.queue_reason != reason:
            req.queue_reason = reason
            self._trace(req, "queued", reason=reason)

    def _slo_violation(
        self, req: ServeRequest, kind: str, value: float, limit_s: float
    ) -> None:
        req.slo_violations += 1
        if req.trace_ctx is not None:
            # Tail sampling: an SLO breach force-records the trace even
            # when the head sampler skipped it.
            req.trace_ctx.escalate("slo")
        obs.event(
            "serve.slo_violation", request=req.id, slo=kind,
            value=round(value, 6), limit_s=limit_s, group=req.group,
            **self._tid(req),
        )
        obs.counter("serve.slo_violations", 1)
        if self._profcap is not None:
            # Direct capture trigger (ISSUE 15): a declared-SLO breach
            # is exactly the moment a device trace answers "why".
            self._profcap.note_slo_breach(kind)

    def _access_write(self, req: ServeRequest, terminal: str) -> None:
        """One access-log line at the request's terminal transition
        (complete or drained). Lazy: the writer opens beside the event
        fragments the first time a recorder-enabled process finishes a
        request — no obs dir, no file."""
        if not self._access_on:
            return
        if self._access is None:
            rec = obs.recorder()
            if rec is None:
                return
            self._access = _ledger.AccessLog(rec.directory, proc=rec.proc)
        ttft = req.ttft_s
        rate = req.decode_tokens_per_s
        self._access.write(
            {
                **self._tid(req),
                "request": req.id,
                "ts": req.t_submit,
                "group": req.group,
                "quant": req.quantize,
                "spec": req.speculative,
                "prompt_len": int(req.prompt.size),
                "max_new_tokens": req.max_new_tokens,
                "bucket": req.bucket,
                "tokens": len(req.tokens),
                "terminal": terminal,
                "finish_reason": req.finish_reason or terminal,
                "queue_wait_s": (
                    None if req.t_admit is None
                    else round(req.t_admit - req.t_submit, 6)
                ),
                "ttft_s": None if ttft is None else round(ttft, 6),
                "itl_s": [round(v, 6) for v in req.itl_s],
                "decode_tokens_per_s": (
                    None if rate is None else round(rate, 2)
                ),
                "slo_violations": req.slo_violations,
                "trace": req.trace,
            }
        )

    def drain_queued(self) -> int:
        """Terminal-trace every still-queued request as ``drained`` (the
        SIGTERM drain path: the process is exiting; queued work rides
        the requeue). The queue itself is untouched — a resumed engine
        can still admit them — but every submitted request's trace now
        reaches exactly one terminal event. Returns the count."""
        n = 0
        for req in self._queue:
            if req.drained:
                continue
            req.drained = True
            self._trace(req, "drained", reason="preempt_drain")
            self._access_write(req, "drained")
            if req.trace_ctx is not None:
                _reqtrace.flush_lifecycle(
                    req.trace_ctx, req.trace, engine_request=req.id
                )
            n += 1
        return n

    def _free_slot(self) -> int | None:
        for s, req in enumerate(self._slots):
            if req is None:
                return s
        return None

    def _admit_one(self, req: ServeRequest, slot: int, span) -> bool:
        """Admit ``req`` into ``slot``. Returns False (request untouched,
        caller leaves it queued) when the page pool cannot fit it —
        token-budget admission backpressure. Page acquisition precedes
        the prefill so a blocked request costs zero device work.
        ``span`` is the caller's ``serve.admit`` span over this call: it
        takes the admission's evidence (slot, bucket, queue wait, pages).

        Disaggregated admission (ISSUE 19): pages covered by an imported
        :class:`~tpuflow.infer.kv_store.KVPageSet` or by lower-tier
        promotions are RESTORED (a masked insert of their committed
        bytes — the admission insert's exact program signature) instead
        of recomputed. When restored + shared pages cover the prompt the
        prefill program never runs: an exact shipped set admits on its
        committed first token (full ship); otherwise the decode program
        is fed ``prompt[L-1]`` at ``lengths = L-1`` — it writes that
        column's kv and emits the first token, bit-equal to prefill by
        the cache-mediated-attention exactness PR 11 pinned (when column
        ``L-1`` lands in a covered page the decode write re-writes
        identical bytes, so shared pages stay sound). A request with
        neither rides the classic path byte-identically."""
        got = self.pool.acquire(req.prompt, self._pages_needed(req))
        if got is None:
            self._note_queued(req, "pages")
            return False
        page_ids, matched = got
        promoted = self.pool.take_promotions()
        now = time.monotonic()
        req.t_admit = now
        W = req.bucket
        L = req.prompt.size
        ps = self.page_size
        pset = req.kv_import
        # Restored pages: logical page index -> bundle, contiguous from
        # where HBM matching broke — tier promotions first, then shipped
        # pages extend the run. A failed tier fetch truncates the run;
        # everything past it rides the prefill write instead (never a
        # drop, never a gap).
        restored: dict[int, dict[str, np.ndarray]] = {}
        restore_src: dict[int, str] = {}
        for j, digest, _tier in promoted:
            if j != matched + len(restored):
                break
            got_b = self.pool.tier.fetch(digest)
            if got_b is None:
                break
            restored[j], restore_src[j] = got_b
        exact = (
            pset is not None
            and pset.n_tokens == L
            and np.array_equal(np.asarray(pset.prompt, np.int32),
                               req.prompt)
        )
        if pset is not None:
            k_full = _kvstore.chain_match(
                pset.digests, self.pool.prefix_digests(req.prompt)
            )
            top = pset.n_pages if exact else min(k_full, pset.n_pages)
            j = matched + len(restored)
            while j < min(top, len(page_ids)):
                restored[j] = pset.page_bundle(j)
                restore_src[j] = "ship"
                j += 1
        covered = matched + len(restored)
        full_ship = exact and pset.tok0 is not None and covered * ps >= L
        feed_decode = (
            not full_ship
            and (pset is not None or self.pool.tier is not None)
            and covered >= 1
            and covered * ps >= L - 1
        )
        mode = (
            "ship" if full_ship else "feed" if feed_decode else "prefill"
        )
        table_row = np.zeros((self.pages_per_slot,), np.int32)
        table_row[: len(page_ids)] = page_ids
        write_mask = np.zeros((self.pages_per_slot,), bool)
        write_mask[matched: len(page_ids)] = True
        for j in restored:
            write_mask[j] = False  # restored bytes, not prefill's
        n_host = sum(1 for s in restore_src.values() if s == "host")
        n_disk = sum(1 for s in restore_src.values() if s == "disk")
        if n_host or n_disk:
            self.pool.tier_hits += n_host + n_disk
            obs.event(
                "serve.tier_hit", request=req.id, host=n_host,
                disk=n_disk, **self._tid(req),
            )
        if restored:
            self._restore_pages(table_row, restored, req.id)
            if n_host or n_disk:
                obs.event(
                    "serve.tier_promote", request=req.id,
                    pages=n_host + n_disk, **self._tid(req),
                )
        first: int | None = None
        row_cache = None
        if mode == "ship":
            first = int(pset.tok0)
            req.t_first = time.monotonic()
            req.t_last_tick = req.t_first
            req.tokens.append(first)
        elif mode == "feed":
            pass  # the first token comes out of the decode block
        else:
            padded = np.full((1, W), self.pad_id, np.int32)
            padded[0, W - L:] = req.prompt
            pads = prompt_lens_to_pad_lens([L], 1, W)
            chunk = normalize_prefill_chunk(self.prefill_chunk, W)
            prefill = self._prefill_q if req.quantize else self._prefill
            prm = self._qparams if req.quantize else self.params
            self._prefill_calls += 1
            with self.ledger.bucket("prefill"), obs.span(
                "serve.prefill", request=req.id, bucket=W,
                prompt_len=int(L), chunk=chunk, quant=bool(req.quantize),
            ):
                tok0, row_cache = prefill(
                    prm, jnp.asarray(padded), pads, chunk=chunk
                )
                first = int(np.asarray(tok0)[0])
            if self.generation is None:
                req.t_first = time.monotonic()
                req.t_last_tick = req.t_first
                req.tokens.append(first)
            else:
                # Every token comes out of a denoise pass: the prefill
                # fills the cache and its own argmax is nobody's token.
                first = None
        req.state = "running"
        extra_trace = {}
        if mode != "prefill" or restored:
            extra_trace = {
                "prefilled": mode,
                "shipped_pages": sum(
                    1 for s in restore_src.values() if s == "ship"
                ),
                "promoted_pages": n_host + n_disk,
            }
        span.set(
            slot=slot, bucket=W, prompt_len=int(L),
            queue_wait_s=round(now - req.t_submit, 6),
            pages=len(page_ids),
            shared_pages=matched,
            **self._tid(req),
        )
        self._trace(
            req, "admitted", slot=slot, bucket=W,
            queue_wait_s=round(now - req.t_submit, 6),
            pages=len(page_ids),
            shared_pages=matched, **extra_trace,
        )
        if first is not None:
            self._note_first_token(req, req.t_first)
            done = (req.eos_id is not None and first == req.eos_id) or (
                req.max_new_tokens == 1
            )
            self._emitted_tokens += 1
            obs.goodput_live().note_serve_tokens(1)
            obs.counter("serve.tokens", 1)
            if done:
                self.pool.release(page_ids)
                self._finish(
                    req, "eos" if req.max_new_tokens > 1 else "budget"
                )
                return True
        if mode == "prefill":
            # Pad-stripped page insert: real prompt kv moves to
            # logical [0, L); shared prefix pages and restored pages
            # are masked OFF the write.
            with self.ledger.bucket("insert"), obs.span(
                "serve.insert", request=req.id
            ):
                self._cache = self._insert(
                    self._cache, row_cache, jnp.asarray(table_row),
                    jnp.int32(W - L), jnp.asarray(write_mask),
                    *self._slot_operand(slot),
                )
        self._page_table[slot] = table_row
        self._slot_pages[slot] = list(page_ids)
        self._lengths[slot] = L if mode != "feed" else L - 1
        self._pads[slot] = 0
        self._slots[slot] = req
        if self.generation is None:
            self._tok[slot] = (
                first if first is not None else int(req.prompt[L - 1])
            )
        else:
            # The prompt's whole blocks are cached (what the insert wrote
            # beyond them, the first block rewrites before anything reads
            # it); the tokens left over open the first block, unmasked.
            left = L % self.generation["block_length"]
            self._lengths[slot] = L - left
            self._tok[slot] = -1
            self._tok[slot, :left] = req.prompt[L - left:]
        self._remaining[slot] = (
            req.max_new_tokens - 1 if first is not None
            else req.max_new_tokens
        )
        self._live[slot] = True
        self._quant[slot] = req.quantize
        self._spec[slot] = req.speculative and self.spec_draft > 0
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        return True

    def _finish(self, req: ServeRequest, reason: str) -> None:
        req.t_done = time.monotonic()
        req.state = "done"
        req.finish_reason = reason
        self._completed += 1
        rate = req.decode_tokens_per_s
        obs.event(
            "serve.complete", request=req.id, mono=req.t_done,
            tokens=len(req.tokens),
            reason=reason, ttft_s=round(req.ttft_s, 6),
            decode_tokens_per_s=None if rate is None else round(rate, 2),
            **self._tid(req),
        )
        obs.counter("serve.requests", 1)
        if req.quantize:
            obs.counter("serve.quant_requests", 1)
        if rate is not None:
            obs.gauge("serve.tokens_per_s", round(rate, 2))
        self._trace(
            req, "complete", reason=reason, tokens=len(req.tokens),
            slo_violations=req.slo_violations,
        )
        self._access_write(req, "complete")
        if req.trace_ctx is not None:
            # Replica half of the cross-process timeline: convert the
            # lifecycle phases to wall-clock spans and flush them to
            # this replica's trace JSONL under the propagated trace id.
            _reqtrace.flush_lifecycle(
                req.trace_ctx, req.trace, engine_request=req.id
            )
        obs.goodput_live().note_serve_complete(req.group)

    def _emit_state_gauges(self) -> None:
        """Queue-depth / occupancy / page-pool gauges on change (plus a
        periodic refresh) — a long idle server must not flood the event
        stream."""
        pool = self.pool
        tier = pool.tier
        state = (
            len(self._queue),
            self.live_slots,
            pool.free_pages,
            pool.prefix_hits,
            None if tier is None else tier.pages_host,
            None if tier is None else tier.pages_disk,
        )
        fr = self.ledger.fractions()
        if self._iters % 64 == 0:
            # Device observatory (ISSUE 15): throttled HBM poll on the
            # fence the scheduler already pays (self-disabling off-TPU;
            # one bool check thereafter), and the capture governor's
            # wall-deadline check for traces armed between decode ticks.
            _device.maybe_emit_hbm()
            if self._profcap is not None:
                self._profcap.poll()
        if state != self._last_gauges or self._iters % 64 == 0:
            self._last_gauges = state
            obs.gauge("serve.queue_depth", state[0])
            obs.gauge(
                "serve.slot_occupancy",
                round(state[1] / self.max_slots, 4),
            )
            obs.gauge("serve.pages_free", state[2])
            obs.gauge("serve.prefix_hits", state[3])
            if tier is not None:
                obs.gauge("serve.pages_host", state[4])
                obs.gauge("serve.pages_disk", state[5])
            # Engine-time ledger fractions (ISSUE 13): the idle /
            # decode / prefill split one babysitter line reads, plus
            # the token-efficiency gauges, sampled on the same
            # change/periodic cadence as the load gauges. verify and
            # decode merge into one "earning tokens" fraction.
            obs.gauge("serve.idle_fraction", round(fr["idle"], 4))
            obs.gauge(
                "serve.decode_fraction",
                round(fr["decode"] + fr["verify"], 4),
            )
            obs.gauge("serve.prefill_fraction", round(fr["prefill"], 4))
            util = self.ledger.decode_utilization
            if util is not None:
                obs.gauge("serve.decode_utilization", round(util, 4))
            read = self.ledger.decode_read_fraction
            if read is not None:
                obs.gauge("serve.decode_read_fraction", round(read, 4))
            waste = self.ledger.masked_row_waste
            if waste is not None:
                obs.gauge("serve.masked_row_waste", round(waste, 4))
        led = obs.goodput_live()
        led.note_serve_state(state[0], state[1], self.max_slots)
        led.note_serve_ledger(
            {
                "idle": fr["idle"],
                "decode": fr["decode"] + fr["verify"],
                "prefill": fr["prefill"],
                "insert": fr["insert"],
                "host_sched": fr["host_sched"],
            },
            utilization=self.ledger.decode_utilization,
            masked_waste=self.ledger.masked_row_waste,
            slo_violations=self.ledger.slo_violations,
            slo_by_group=self.ledger.slo_by_group,
        )
        led.note_serve_pages(pool.free_pages, pool.usable_pages)
        led.note_serve_prefix(pool.prefix_hits, pool.prefix_lookups)
        led.note_serve_role(self.role)
        if tier is not None:
            led.note_serve_tiers(
                tier.pages_host, tier.pages_disk, pool.tier_hits
            )

    def _decode_rung(self, mask) -> tuple[np.ndarray, int]:
        """The operand shapes of one group's decode block, from what the
        host holds: the first of ``decode_shapes`` that holds the
        group's live slots and the frontier the block can reach, the
        longest live row plus the positions a call can add (``decode_block``
        tokens, or its whole blocks under ``generation``). Returns the
        slots it runs over — the group's live slots first, then dead
        ones, then (only where those run out) other groups' live ones —
        and the pages it reads of each."""
        live = int(mask.sum())
        reach = min(
            int(self._lengths[mask].max()) + self._advance, self.n_ctx
        )
        n_rows, pages = next(
            (r, w) for r, w in self.decode_shapes
            if r >= live and w * self.page_size >= reach
        )
        return np.lexsort((self._live, ~mask))[:n_rows], pages

    def _run_decode_block(self, quant: bool, spec: bool = False) -> int:
        """One decode (or speculative verify) block over ONE group's
        slots — the groups partition the live set by (numeric path,
        speculative): run that group's persistent program on the rows
        ``_decode_rung`` picks (a verify block: every slot, full
        width) with every row outside the group masked out of the live
        set, merge the group's per-slot state back by index, harvest
        tokens, free exited slots. Returns emitted token count.

        Why masking composes: each slot row only ever attends within its
        own pages, and a program only
        advances (and only writes real k/v for) rows live in ITS set — a
        masked-out row's garbage k/v writes land at its frozen
        ``lengths`` column onward (or, beyond the block's read width,
        in the trash page), exactly where that row's OWN program
        writes real k/v next, so they are always overwritten before
        anything can attend to them (a verify block's K+1 garbage
        columns sit beyond the frozen frontier — masked out of every
        query until overwritten, the same argument the solo ladder's
        rewind rests on). Mixed fp+int8+speculative traffic therefore
        shares one cache and one engine with zero cross-talk (pinned by
        tests/test_serve.py)."""
        mask = self._live & (self._quant == quant) & (self._spec == spec)
        if not mask.any():
            return 0
        prm = self._qparams if quant else self.params
        old_remaining = self._remaining.copy()
        group_live = int(mask.sum())
        total_live = int(self._live.sum())
        if spec:
            rows, pages = np.arange(self.max_slots), self.pages_per_slot
        else:
            rows, pages = self._decode_rung(mask)
        # The whole block — host drafts, device dispatch, the fence, the
        # state merge — charges to the decode (or verify) ledger bucket;
        # everything between blocks lands in host_sched by construction.
        # The span's three children split it: only the fence waits for
        # the device.
        with self.ledger.bucket("verify" if spec else "decode"), obs.span(
            "serve.decode", slots=group_live, spec=spec, quant=quant,
            rows=len(rows), pages=pages,
        ) as sp:
            with obs.span("serve.decode.dispatch"):
                tok, lengths, pads, remaining, live, eos = (
                    a[rows] for a in (
                        self._tok, self._lengths, self._pads,
                        self._remaining, mask, self._eos,
                    )
                )
                table = self._page_table[rows, :pages]
                if spec:
                    # Host-side prompt-lookup drafts per slot (a wrong
                    # draft only costs speed; the verify forward
                    # arbitrates).
                    K = self.spec_draft
                    drafts = np.zeros((self.max_slots, K), np.int32)
                    for s in np.nonzero(mask)[0]:
                        req = self._slots[int(s)]
                        hist = np.concatenate(
                            [req.prompt, np.asarray(req.tokens, np.int32)]
                        )
                        drafts[s] = ngram_draft(
                            hist, K, ngram=self.spec_ngram
                        )
                    verify = self._verify_q if quant else self._verify
                    (
                        self._cache, toks, tok, lengths, remaining, live
                    ) = verify(
                        prm, self._cache, jnp.asarray(table), tok,
                        jnp.asarray(drafts), lengths, pads, remaining,
                        live, eos,
                    )
                else:
                    decode = self._decode_q if quant else self._decode
                    (
                        self._cache, toks, tok, lengths, remaining, live,
                        steps, *when,
                    ) = decode(
                        prm, self._cache, tok, lengths, pads, remaining,
                        live, eos, table, *self._slot_operand(rows),
                    )
            # The host copy of the block's tokens IS the fence.
            with obs.span("serve.decode.fence"):
                toks = np.asarray(toks)
                if not spec:
                    sums, maxes = (
                        {k: v.item() for k, v in d.items()}
                        for d in jax.device_get(steps)
                    )
                    sp.set(**sums, **maxes)
                    self.ledger.note_model_steps(
                        self.decode_block, sums, maxes
                    )
            # Merge by index, the group's rows alone — the program's
            # carries hold pad_id tokens for every row outside its live
            # set, the OTHER groups' mid-flight slots among them.
            with obs.span("serve.decode.merge"):
                ours = mask[rows]
                at = rows[ours]
                self._tok[at] = np.asarray(tok)[ours]
                self._lengths[at] = np.asarray(lengths)[ours]
                self._remaining[at] = np.asarray(remaining)[ours]
                self._live[at] = np.asarray(live)[ours]
                by_slot = np.full(
                    (self.max_slots, toks.shape[1]), self.pad_id, toks.dtype
                )
                by_slot[rows] = toks
                passes_by_slot = None
                if not spec and when:  # block diffusion: each token's pass
                    passes_by_slot = np.zeros(by_slot.shape, np.int32)
                    passes_by_slot[rows] = np.asarray(when[0])
            emitted = int((old_remaining - self._remaining).sum())
            # Forward passes of the call, and those of them that computed
            # no head (a block's commit): tokens and passes are two counts
            # since a pass may yield none or several.
            passes = 1 if spec else self.decode_block
            commits = 0 if self.generation is None else (
                passes // (self.generation["denoise_steps"] + 1)
            )
            sp.set(tokens=emitted, passes=passes, commit_passes=commits)
            self.ledger.note_decode_block(
                self.max_slots, group_live, total_live, spec=spec,
                drafted=group_live * self.spec_draft if spec else 0,
                committed=emitted,
                read_positions=len(rows) * pages * self.page_size,
                full_positions=self.max_slots * self.n_ctx,
                passes=passes, commit_passes=commits,
            )
            rate = self.ledger.tokens_per_pass
            if rate is not None:
                obs.gauge("serve.tokens_per_pass", round(rate, 4))
            if spec:
                self._spec_committed += emitted
                self._spec_forwards += group_live
                rate = self._spec_committed / max(self._spec_forwards, 1)
                obs.gauge("serve.spec_accept_rate", round(rate, 4))
                obs.goodput_live().note_serve_spec(
                    self._spec_committed, self._spec_forwards
                )
        with obs.span("serve.harvest"):
            self._harvest(
                mask, by_slot, old_remaining - self._remaining, spec,
                passes_by_slot,
            )
        return emitted

    def _harvest(self, mask, toks, emitted_by_row, spec: bool,
                 passes=None) -> None:
        """Hand a block's tokens to their requests (and, under block
        diffusion, the denoise pass that unmasked each: ``passes``), note
        the per-token latencies, and free the slots of requests that
        ended."""
        now = time.monotonic()
        led = obs.goodput_live()
        for s, req in enumerate(self._slots):
            if req is None or not mask[s]:
                continue
            n = int(emitted_by_row[s])
            if n:
                req.tokens.extend(int(t) for t in toks[s, :n])
                if passes is not None:
                    req.token_passes.extend(int(t) for t in passes[s, :n])
                # One ITL observation per tick (tick wall / tokens
                # committed): the per-token latency the SLO gate,
                # /metrics percentiles, and the access log all share.
                anchor = (
                    req.t_last_tick
                    if req.t_last_tick is not None else req.t_first
                )
                itl = None
                if anchor is not None:
                    itl = max(now - anchor, 0.0) / n
                    req.itl_s.append(itl)
                    self.ledger.note_itl(req.group, itl)
                    ctx = req.trace_ctx
                    led.note_serve_itl(
                        itl,
                        trace_id=(
                            ctx.trace_id
                            if ctx is not None and ctx.recorded
                            else None
                        ),
                    )
                    if self._profcap is not None:
                        # Median+MAD ITL spike detector (ISSUE 15); the
                        # same call advances a live capture's bound.
                        self._profcap.observe_itl(itl)
                if req.t_first is None:
                    # Prefill-free admission (ISSUE 19): the request's
                    # first token came out of the decode program, so
                    # TTFT lands on this harvest — after the ITL anchor
                    # above, which must not see a zero-width tick.
                    self._note_first_token(req, now)
                req.t_last_tick = now
                if spec:
                    self._trace(
                        req, "tick", tokens=n, spec=True,
                        drafted=self.spec_draft, accepted=n - 1,
                    )
                else:
                    self._trace(req, "tick", tokens=n, spec=False)
                if itl is not None and self.ledger.check_itl(
                    itl, group=req.group
                ):
                    self._slo_violation(
                        req, "itl", itl, self.ledger.slo_itl_s
                    )
            if not self._live[s]:
                last = req.tokens[-1] if req.tokens else None
                if req.eos_id is not None and last == req.eos_id:
                    reason = "eos"
                elif len(req.tokens) >= req.max_new_tokens:
                    reason = "budget"
                else:
                    reason = "capacity"  # n_ctx frontier hit
                self._finish(req, reason)
                self._slots[s] = None
                self._quant[s] = False
                self._spec[s] = False
                self.pool.release(self._slot_pages[s])
                self._slot_pages[s] = []
                self._page_table[s, :] = 0

    @property
    def spec_accept_rate(self) -> float | None:
        """Cumulative tokens committed per speculative verify, per row
        (1.0 = speculation bought nothing; draft_len + 1 is the max)."""
        if not self._spec_forwards:
            return None
        return self._spec_committed / self._spec_forwards

    def step(self, admit: bool = True) -> bool:
        """One scheduler iteration: admit waiting requests into free
        slots (chunked prefill; the page pool has to
        fit too — a blocked head-of-queue request applies backpressure),
        then run one decode block per live group — (fp, int8) x (plain,
        speculative). Returns False when there was nothing to do."""
        self._iters += 1
        did = False
        with obs.span("serve.step"):
            while admit and self._queue:
                slot = self._free_slot()
                if slot is None:
                    self._note_queued(self._queue[0], "slots")
                    break
                req = self._queue[0]
                with obs.span("serve.admit", request=req.id) as sp:
                    admitted = self._admit_one(req, slot, sp)
                    sp.set(admitted=admitted)
                if not admitted:
                    break  # page backpressure: queued, never dropped
                self._queue.popleft()
                did = True
            if self._live.any():
                did = True
                emitted = 0
                for quant in (False, True) if self.quant_mode else (False,):
                    for spec in (
                        (False, True) if self.spec_draft else (False,)
                    ):
                        emitted += self._run_decode_block(quant, spec)
                self._emitted_tokens += emitted
                obs.goodput_live().note_serve_tokens(emitted)
                if emitted:
                    obs.counter("serve.tokens", emitted)
            self._emit_state_gauges()
        return did

    def run_until_idle(self, max_iters: int | None = None) -> None:
        """Drive the scheduler until queue and slots are empty."""
        iters = 0
        while self._queue or self._live.any():
            self.step()
            iters += 1
            if max_iters is not None and iters >= max_iters:
                raise RuntimeError(
                    f"engine not idle after {max_iters} iterations "
                    f"(queue={len(self._queue)}, live={self.live_slots})"
                )

    def generate_many(
        self,
        prompts,
        *,
        max_new_tokens: int,
        eos_id: int | None = None,
        quantize: bool = False,
        speculative: bool | None = None,
    ) -> list[np.ndarray]:
        """Submit every prompt, run to completion, return each request's
        generated tokens in submit order (the batch-predictor adapter)."""
        reqs = [
            self.submit(
                p, max_new_tokens=max_new_tokens, eos_id=eos_id,
                quantize=quantize, speculative=speculative,
            )
            for p in prompts
        ]
        self.run_until_idle()
        return [r.result() for r in reqs]

    # ---------------------------------------------------------------- warmup
    def _slot_operand(self, slots) -> tuple:
        """The insert's and the decode program's last operand, a model
        with slot state's alone: the slot (or the rows' slots) whose state
        rows the program writes. Other models' programs keep the
        signature they had."""
        if not self.slot_state:
            return ()
        return (jnp.asarray(slots, jnp.int32),)

    def _insert_warm_args(self):
        """The insert call's non-cache operands for a warmup/AOT pass:
        one full table of trash-routed pages (table zeros + mask all-on
        exercises the real scatter against the reserved page), and the
        trash state row."""
        return (
            jnp.zeros((self.pages_per_slot,), jnp.int32),
            jnp.int32(0),
            jnp.ones((self.pages_per_slot,), bool),
            *self._slot_operand(self.max_slots),
        )

    def _decode_variants(self) -> list[tuple[str, int, int]]:
        """Every (rows, pages) shape a decode block can take, each
        under the name the device ledger files it by (rows x positions,
        as ``prefill@<width>`` names a bucket)."""
        return [
            (f"@{r}x{w * self.page_size}", r, w)
            for r, w in self.decode_shapes
        ]

    def _decode_warm_args(self, n_rows: int, pages: int):
        """Dead-row operands for one decode warmup execution at one shape
        of the ladder: tok, lengths, pads, remaining, live, eos,
        page_table (and, a model with slot state, the trash row's
        slots)."""
        zeros = np.zeros((n_rows,), np.int32)
        return [
            np.zeros((n_rows,) + self._tok.shape[1:], np.int32),
            zeros, zeros, zeros, np.zeros((n_rows,), bool),
            np.full((n_rows,), -1, np.int32),
            np.zeros((n_rows, pages), np.int32),
            *self._slot_operand(np.full((n_rows,), self.max_slots)),
        ]

    def warmup(self) -> dict[str, int]:
        """Compile-or-load every program the engine will ever run: the
        decode block at every shape of ``decode_ladder`` (and the
        speculative verify block when armed), the
        insert, and one prefill per bucket — through the persistent
        compile cache (``maybe_enable_compile_cache``), so a server
        restart pays cache loads, not compiles. Executes each program once on
        dead-slot state (guaranteed jit-cache hits afterwards; the
        garbage forwards are masked by ``live=False`` everywhere — their
        writes land in the trash page) and restores a pristine cache.
        Returns ``compile_stats()``."""
        from tpuflow.dist import maybe_enable_compile_cache

        maybe_enable_compile_cache()
        # One child span for each program's first run: its trace, lowering
        # and compile-or-load (the compile listener's spans nest under
        # it) and its dispatch. The warm-up device ledger (ISSUE 15)
        # files each span's duration as that program's compile_s.
        ledger_on = obs.recorder() is not None and knobs.get_bool(
            "TPUFLOW_DEVICE_LEDGER"
        )
        warmed: list[tuple[str, Any]] = []

        def program(name: str, entry: str | None = None, **shape):
            sp = obs.span("serve.warmup.program", program=name, **shape)
            if ledger_on and entry is not None:
                warmed.append((entry, sp))
            return sp

        with obs.span(
            "serve.warmup", buckets=len(self.buckets),
            quant=self.quant_mode or "off", spec=self.spec_draft,
        ) as sp:
            row_cache = None
            for w in self.buckets:
                chunk = normalize_prefill_chunk(self.prefill_chunk, w)
                with program("prefill", f"prefill@{w}", bucket=w):
                    _, row_cache = self._prefill(
                        self.params,
                        jnp.zeros((1, w), jnp.int32),
                        prompt_lens_to_pad_lens([w], 1, w),
                        chunk=chunk,
                    )
                if self.quant_mode is not None:
                    # The int8 prefill ladder compiles beside the fp one
                    # — a quantize=True admission must be a cache hit.
                    with program("prefill_q", f"prefill_q@{w}", bucket=w):
                        _, row_cache = self._prefill_q(
                            self._qparams,
                            jnp.zeros((1, w), jnp.int32),
                            prompt_lens_to_pad_lens([w], 1, w),
                            chunk=chunk,
                        )
            if row_cache is not None:
                # First insert: the fresh (uncommitted) init cache.
                with program("insert", "insert"):
                    self._cache = self._insert(
                        self._cache, row_cache, *self._insert_warm_args()
                    )
            for at, n_rows, pages in self._decode_variants():
                with program("decode", f"decode{at}", rows=n_rows, pages=pages):
                    out = self._decode(
                        self.params, self._cache,
                        *self._decode_warm_args(n_rows, pages),
                    )
                    self._cache = out[0]
            if self.spec_draft:
                # The verify block (and below, its int8 twin): dead-slot
                # drafts of zeros exercise the exact (S, K+1) signature
                # the speculative scheduler replays.
                zdraft = jnp.zeros(
                    (self.max_slots, self.spec_draft), jnp.int32
                )
                with program(
                    "verify", "verify",
                    rows=self.max_slots, pages=self.pages_per_slot,
                ):
                    out = self._verify(
                        self.params, self._cache,
                        jnp.asarray(self._page_table), self._tok, zdraft,
                        self._lengths, self._pads, self._remaining,
                        self._live, self._eos,
                    )
                    self._cache = out[0]
            if self.quant_mode is not None:
                # The int8 decode block on the decode-committed cache —
                # the exact signature the mixed-traffic scheduler replays.
                for at, n_rows, pages in self._decode_variants():
                    with program(
                        "decode_q", f"decode_q{at}", rows=n_rows, pages=pages
                    ):
                        out = self._decode_q(
                            self._qparams, self._cache,
                            *self._decode_warm_args(n_rows, pages),
                        )
                        self._cache = out[0]
                if self.spec_draft:
                    with program(
                        "verify_q", "verify_q",
                        rows=self.max_slots, pages=self.pages_per_slot,
                    ):
                        out = self._verify_q(
                            self._qparams, self._cache,
                            jnp.asarray(self._page_table), self._tok, zdraft,
                            self._lengths, self._pads, self._remaining,
                            self._live, self._eos,
                        )
                        self._cache = out[0]
            if row_cache is not None:
                # Second insert: the steady-state signature — a cache
                # COMMITTED by the decode program (with sharded params
                # the jit key differs from the fresh-zeros variant; both
                # must be warm or the first post-decode admission would
                # recompile, breaking the never-recompile contract).
                with program("insert"):
                    self._cache = self._insert(
                        self._cache, row_cache, *self._insert_warm_args()
                    )
            # Warmup wrote garbage k/v into slot 0's columns; every query
            # of a future occupant is masked to its own [pad, length]
            # window and the insert overwrites the row, but start zeroed
            # anyway so warmup is observationally a no-op. x*0 (not a
            # fresh zeros tree): the result stays committed exactly like
            # every later decode/insert output, so the program signatures
            # warmed above are the ones the serving loop replays. In
            # place, the cache donated: a new tree beside the old one
            # would need the cache's bytes twice.
            with program("reset"):
                self._cache = jax.jit(
                    lambda cache: jax.tree_util.tree_map(
                        lambda x: x * 0, cache
                    ),
                    donate_argnums=0,
                )(self._cache)
                jax.block_until_ready(self._cache)
            stats = self.compile_stats()
            sp.set(**stats)
        if warmed:
            # A few buffered events and one small JSON write, nothing on
            # the serving hot path.
            try:
                ledger = _device.ProgramLedger(source="warmup")
                for entry, wsp in warmed:
                    ledger.note_entry(
                        {"name": entry, "compile_s": round(wsp.dur_s, 4)}
                    )
                ledger.write()
            except Exception as e:
                print(
                    f"[tpuflow] warmup device ledger failed (ignored): "
                    f"{e!r}"
                )
        return stats

    def aot_lower(self, ledger=None) -> int:
        """AOT-lower (``jit(...).lower(...).compile()``) every program
        signature this engine replays — the decode block at each shape
        of its ladder (``decode@<rows>x<positions>``), speculative
        verify, page insert, and each bucket's prefill, plus the
        int8 twins on a quant-armed engine — WITHOUT executing anything
        (row caches come from ``eval_shape``). With the persistent
        compile cache enabled the executables land on disk, which is
        ``tools/prewarm_cache.py``'s whole job; the engine owns the
        signature list so the tool can't drift from the programs the
        scheduler actually runs (every bucket can host a short-enough
        prompt: capacity is the real length). Returns the program count.

        ``ledger`` (a ``tpuflow.obs.device.ProgramLedger``) records each
        compiled program's wall-s + cost/memory analysis as it lands —
        the AOT path holds the only object carrying both analyses, and
        lowering here never touches the jit dispatch cache, so
        ``compile_stats()`` is bitwise unchanged by ledger collection."""

        def _compile(name, lowered):
            t0 = time.monotonic()
            compiled = lowered.compile()
            if ledger is not None:
                ledger.note_compiled(
                    name, compiled, compile_s=time.monotonic() - t0
                )
            return compiled

        pairs = [
            ("", self._prefill, self._decode, self._verify, self.params)
        ]
        if self.quant_mode is not None:
            pairs.append(
                ("_q", self._prefill_q, self._decode_q, self._verify_q,
                 self._qparams)
            )
        programs = 0
        row_shape = None
        for suffix, prefill, decode, verify, prm in pairs:
            for at, n_rows, pages in self._decode_variants():
                _compile(
                    f"decode{suffix}{at}",
                    decode.lower(
                        prm, self._cache,
                        *self._decode_warm_args(n_rows, pages),
                    ),
                )
                programs += 1
            if verify is not None:
                _compile(
                    f"verify{suffix}",
                    verify.lower(
                        prm, self._cache, jnp.asarray(self._page_table),
                        self._tok,
                        jnp.zeros(
                            (self.max_slots, self.spec_draft), jnp.int32
                        ),
                        self._lengths, self._pads, self._remaining,
                        self._live, self._eos,
                    ),
                )
                programs += 1
            for w in self.buckets:
                chunk = normalize_prefill_chunk(self.prefill_chunk, w)
                pf_args = (
                    prm,
                    jnp.zeros((1, w), jnp.int32),
                    prompt_lens_to_pad_lens([w], 1, w),
                )
                _compile(
                    f"prefill{suffix}@{w}",
                    prefill.lower(*pf_args, chunk=chunk),
                )
                programs += 1
                row_shape = jax.eval_shape(
                    functools.partial(prefill, chunk=chunk), *pf_args
                )[1]
        if row_shape is not None:
            # The insert signature (abstract row cache from eval_shape —
            # no prefill ever executes). The decode-committed second
            # signature only diverges under sharded params; the engine's
            # own warmup() covers it at server start.
            _compile(
                "insert",
                self._insert.lower(
                    self._cache, row_shape, *self._insert_warm_args()
                ),
            )
            programs += 1
        return programs

    def collect_program_ledger(self, path: str | None = None):
        """The engine's device ledger (ISSUE 15): AOT-compile every
        signature through :meth:`aot_lower` with a recording ledger,
        run the static HBM budget check, and persist ``programs.json``
        (default: beside the recorder's event fragments). With the
        persistent compile cache enabled the recompiles are cache
        loads. The AOT path never touches the jit dispatch cache, so
        ``compile_stats()`` is identical before and after — pinned by
        tests/test_serve.py. Returns the ledger."""
        ledger = _device.ProgramLedger(source="serve")
        self.aot_lower(ledger=ledger)
        ledger.budget_check()
        ledger.write(path)
        return ledger


def serve_forever(
    engine: ServeEngine,
    *,
    idle_sleep_s: float = 0.005,
    max_s: float | None = None,
    should_stop=None,
) -> None:
    """Long-lived serving loop reusing the gang machinery: heartbeat
    stamps every iteration (the supervisor's stall detector works on a
    serving gang exactly as on a training gang), the live ``/metrics`` +
    ``/status`` exporter starts when ``TPUFLOW_OBS_HTTP_PORT`` is set
    (export start also stamps this replica into
    ``TPUFLOW_FLEET_REGISTRATION_DIR`` when configured, so a fleet
    observatory discovers it — ISSUE 14), and a SIGTERM preemption
    drains — stops admitting, finishes the live slots, exits — instead
    of killing requests mid-decode.

    With ``TPUFLOW_ROUTER_GATEWAY`` armed (the default) the loop also
    starts a ``ReplicaGateway`` — the replica-side ``/generate``
    endpoint the front-door router forwards to — sharing the step
    loop's lock (submit and step interleave safely) and advertising its
    URL as ``generate_url`` in this process's ``/status`` snapshot, so
    the fleet row the router picks carries a forwardable address.

    ``max_s`` bounds the loop (tests / bounded jobs); ``should_stop`` is
    an optional callable polled each iteration.
    """
    from tpuflow.utils import heartbeat, preempt

    obs.maybe_start_export()
    step_lock = threading.RLock()
    gateway = None
    if knobs.get_bool("TPUFLOW_ROUTER_GATEWAY"):
        # Production ingress (ISSUE 17): without this, every fleet row
        # is status-only and the router's http_forward has nothing to
        # POST to. Ephemeral port — the URL travels via /status, no
        # static port to collide on. Bind host follows the /status
        # exporter's knob so both endpoints share reachability.
        from tpuflow.infer.frontdoor import ReplicaGateway

        gw_host = knobs.raw("TPUFLOW_OBS_HTTP_HOST", "127.0.0.1")
        try:
            gateway = ReplicaGateway(
                engine, lock=step_lock, host=gw_host
            )
        except OSError as e:
            print(
                f"[tpuflow] replica gateway failed to bind on "
                f"{gw_host} ({e}); serving status-only"
            )
        else:
            url = gateway.url
            if gw_host == "0.0.0.0":  # noqa: S104 (operator knob)
                import socket as _socket
                from urllib.parse import urlsplit

                port = urlsplit(url).port
                url = (
                    f"http://{_socket.gethostname()}:{port}/generate"
                )
            obs.goodput_live().note_serve_generate_url(url)
    if obs.recorder() is not None and knobs.get_bool(
        "TPUFLOW_DEVICE_LEDGER"
    ):
        # Device observatory (ISSUE 15): the full per-program
        # cost/memory ledger at server start — with the persistent
        # compile cache warm (warmup() just enabled it) the AOT
        # recompiles are cache loads, and an operator sees every
        # program's HBM footprint (plus the static budget verdict)
        # BEFORE traffic arrives.
        try:
            engine.collect_program_ledger()
        except Exception as e:
            print(
                f"[tpuflow] device program ledger failed (ignored): {e!r}"
            )
    preempt.install_sigterm_handler()
    deadline = None if max_s is None else time.monotonic() + max_s
    draining = False
    try:
        while True:
            if preempt.preemption_requested() and not draining:
                # Drain hook (ISSUE 17): flip the exported flag the same
                # iteration admissions stop, so the front-door router
                # sees ``serve_draining`` on the next /status poll and
                # re-routes queued work instead of waiting for
                # staleness to prove a death that is actually a drain.
                draining = True
                obs.goodput_live().note_serve_draining(True)
                if gateway is not None:
                    # New /generate requests 503 "draining" at once —
                    # the router re-dispatches instead of queueing work
                    # on a replica that will never admit it.
                    gateway.draining = True
            with step_lock:
                did = engine.step(admit=not draining)
            heartbeat.beat(step=engine._iters)
            if draining and not engine._live.any():
                # Queued requests ride the requeue; their traces reach
                # the drained terminal so no submitted request vanishes
                # from the access log (ISSUE 13).
                with step_lock:
                    engine.drain_queued()
                return
            if should_stop is not None and should_stop():
                return
            if deadline is not None and time.monotonic() > deadline:
                return
            if not did:
                if draining:
                    with step_lock:
                        engine.drain_queued()
                    return
                with engine.ledger.bucket("idle"):
                    time.sleep(idle_sleep_s)
    finally:
        if gateway is not None:
            # Retract the advertised URL before the socket dies so a
            # fleet poll racing the shutdown never hands the router an
            # address that can only ever refuse.
            obs.goodput_live().note_serve_generate_url(None)
            gateway.close()
        # Run registry (ISSUE 16): whatever ended the loop — drain,
        # stop callable, deadline, or an exception on its way out —
        # this replica's headline (requests, TTFT/ITL percentiles from
        # the mergeable buckets, SLO count) lands in the cross-run
        # registry when TPUFLOW_REGISTRY_PATH is armed. One knob read
        # when it is not; never masks the in-flight exception.
        from tpuflow.obs import registry as registry_mod

        registry_mod.maybe_append_live("serve")
