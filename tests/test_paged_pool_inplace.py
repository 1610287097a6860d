"""The paged KV pool is ONE buffer that every engine program updates in
place by index (ISSUE 27): the decode and verify programs carry it whole
through the layer scan and the step scan, the insert scatters a row's
pages into it, and nothing slices a layer out of it, stacks layers back
into it or selects over it.

- **Structure, read off the jaxpr** (no chip): under ``scan_layers`` no
  scan has the stacked pool among its scanned inputs or outputs, and both
  loops have it in their carry; the insert has no pool-sized ``select_n``,
  ``dynamic_update_slice`` or ``dynamic_slice``. This is the guard that
  keeps a later refactor from putting the copies back.
  A decode block traced at fewer rows or pages than the engine's all
  (ISSUE 33) gathers ``(rows, width, H, D)`` and, at less than the full
  width, no gather or product in it has the extent of ``n_ctx``: the
  guard that keeps the full read from coming back.
- **Exactness over both cache layouts** (one pool per block, and the
  layer-stacked pool the benchmark's cell serves): engine tokens equal
  solo ``generate()``; a shared prefix page is never rewritten by a
  matching admission; a masked-off insert touches the trash page alone;
  export -> import of a page set round-trips bit for bit.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuflow.infer import generate
from tpuflow.infer.serve import ServeEngine
from tpuflow.models.gpt2 import GPT2, GPT2Config

PAGE = 8


@pytest.fixture(scope="module", params=[False, True], ids=["blocks", "scan"])
def rig(request, tmp_path_factory):
    """(model, params, warmed 2-slot paged engine with a KV store) for
    one cache layout."""
    cfg = GPT2Config.small_test(
        n_ctx=64, n_layer=3, dropout=0.0, scan_layers=request.param,
        remat=request.param,
    )
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[16, 32], decode_block=4,
        page_size=PAGE,
        kv_store_dir=str(tmp_path_factory.mktemp("kvstore")),
    )
    eng.warmup()
    return model, params, eng


def _solo(model, params, prompt, n_new):
    return np.asarray(
        generate(
            model, params, np.asarray(prompt, np.int32)[None, :],
            max_new_tokens=n_new, temperature=0.0,
        )
    )[0]


def _pool_leaves(eng) -> dict[str, np.ndarray]:
    """Host copies of the pool's K/V leaves with the page axis first."""
    return {
        key: np.moveaxis(np.asarray(leaf), leaf.ndim - 4, 0)
        for key, leaf in eng._cache_leaf_items(eng._cache)
    }


# ------------------------------------------------------------- structure
def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, remat, cond) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _has_elems(var, n: int) -> bool:
    shape = getattr(var.aval, "shape", None)
    return shape is not None and math.prod(shape) == n


def _cold_engine(n_ctx=32, max_slots=2, **config):
    """An engine that is only traced (zero weights, nothing compiled),
    and the number of elements of one of its pool leaves."""
    model = GPT2(
        GPT2Config.small_test(n_ctx=n_ctx, n_layer=3, dropout=0.0, **config)
    )
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0),
        ),
    )
    eng = ServeEngine(
        model, params, max_slots=max_slots, buckets=[16], decode_block=2,
        page_size=PAGE, speculative=2,
    )
    sizes = {
        math.prod(leaf.shape) for _, leaf in eng._cache_leaf_items(eng._cache)
    }
    assert len(sizes) == 1
    return eng, sizes.pop()


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("program", ["decode", "verify"])
def test_layer_scan_carries_the_pool_and_scans_none_of_it(program, remat):
    eng, n_pool = _cold_engine(scan_layers=True, remat=remat)
    assert n_pool == 3 * eng.n_pages * PAGE * 4 * 32  # the whole stack
    table = jnp.asarray(eng._page_table)
    if program == "decode":
        jaxpr = jax.make_jaxpr(eng._decode)(
            eng.params, eng._cache,
            *eng._decode_warm_args(eng.max_slots, eng.pages_per_slot),
        )
        loops = 2  # the step scan and the layer scan inside it
    else:
        jaxpr = jax.make_jaxpr(eng._verify)(
            eng.params, eng._cache, table, eng._tok,
            jnp.zeros((eng.max_slots, eng.spec_draft), jnp.int32),
            eng._lengths, eng._pads, eng._remaining, eng._live, eng._eos,
        )
        loops = 1
    carrying = 0
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        first_x = eqn.params["num_consts"] + eqn.params["num_carry"]
        scanned = eqn.invars[first_x:] + eqn.outvars[eqn.params["num_carry"]:]
        assert not [v for v in scanned if _has_elems(v, n_pool)], (
            "the pool is scanned in or out of a loop: every iteration "
            "slices it and writes it back"
        )
        carried = [
            v for v in eqn.outvars[: eqn.params["num_carry"]]
            if _has_elems(v, n_pool)
        ]
        if carried:
            assert len(carried) == 2  # K and V
            carrying += 1
    assert carrying == loops
    # The writes are scatters of rows into the whole pool; nothing moves
    # a layer of it.
    n_layer = n_pool // 3
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name in ("dynamic_slice", "dynamic_update_slice"):
            assert not [
                v for v in list(eqn.invars) + list(eqn.outvars)
                if hasattr(v, "aval")
                and (_has_elems(v, n_pool) or _has_elems(v, n_layer))
            ], eqn


@pytest.mark.parametrize("scan_layers", [False, True], ids=["blocks", "scan"])
@pytest.mark.parametrize(
    "rung", [(1, 16), (2, 24), (2, 32), (4, 32)], ids=str
)
def test_decode_variant_reads_its_rung_and_no_more(rung, scan_layers):
    """The decode program traced at one shape (rows, pages), of the
    engine's ladder or not: every page gather yields ``(rows, pages,
    page_size, H, D)`` and, at less than the full width, no gather or
    product touches an operand with an extent of ``n_ctx`` positions.
    Sizes chosen so that no model width (4 heads of 32, 128 wide, 512
    in the feed-forward and the vocabulary) equals a read width (128,
    192 positions) or ``n_ctx`` (256)."""
    n_ctx, slots = 256, 4
    eng, _ = _cold_engine(
        n_ctx=n_ctx, max_slots=slots, scan_layers=scan_layers
    )
    assert eng.decode_shapes == [(1, 16), (1, 32), (2, 32), (4, 32)]
    rows, pages = rung
    jaxpr = jax.make_jaxpr(eng._decode)(
        eng.params, eng._cache, *eng._decode_warm_args(rows, pages)
    )
    width = pages * PAGE
    gathered, extents = [], set()
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name not in ("gather", "dot_general"):
            continue
        shapes = [
            tuple(v.aval.shape)
            for v in list(eqn.invars) + list(eqn.outvars)
            if hasattr(v, "aval") and hasattr(v.aval, "shape")
        ]
        if eqn.primitive.name == "gather" and len(shapes[-1]) == 5:
            gathered.append(shapes[-1])
        # The position table (n_ctx, n_embd) is looked up by a gather
        # too: its operand is two-dimensional and no part of the read.
        extents.update(d for sh in shapes if len(sh) > 2 for d in sh)
    layers = 1 if scan_layers else 3  # the layer scan's body is one block
    assert gathered == [(rows, pages, PAGE, 4, 32)] * (2 * layers)
    assert width in extents  # the scores' and the weighted sum's products
    assert (n_ctx in extents) == (pages == n_ctx // PAGE)


@pytest.mark.parametrize("scan_layers", [False, True], ids=["blocks", "scan"])
def test_insert_is_one_scatter_per_leaf(scan_layers):
    eng, n_pool = _cold_engine(scan_layers=scan_layers)
    leaves = eng._cache_leaf_items(eng._cache)
    jaxpr = jax.make_jaxpr(eng._insert)(
        eng._cache, eng._row_template(), *eng._insert_warm_args()
    )
    scatters = 0
    for eqn in _eqns(jaxpr.jaxpr):
        big = [
            v for v in eqn.invars if hasattr(v, "aval") and _has_elems(v, n_pool)
        ]
        if not big:
            continue
        assert eqn.primitive.name in ("scatter", "reshape", "jit"), (
            f"a pool-sized operand of {eqn.primitive.name}: the insert "
            "reads or rewrites the whole pool"
        )
        scatters += eqn.primitive.name == "scatter"
    assert scatters == len(leaves)


# -------------------------------------------- exactness over both layouts
def test_engine_tokens_equal_generate_and_never_recompile(rig):
    """Four unequal requests through two slots (admissions wait on
    evictions, slots and pages are reused), submitted while others
    decode: every request equals its solo generate()."""
    model, params, eng = rig
    base = eng.compile_stats()
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, 512, size=L).astype(np.int32)
        for L in (3, 16, 21, 9)
    ]
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts[:3]]
    eng.step()
    reqs.append(eng.submit(prompts[3], max_new_tokens=9))
    eng.run_until_idle(max_iters=200)
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(r.result(), _solo(model, params, p, 9))
    assert eng.compile_stats() == base, "engine recompiled after warmup"
    assert eng.pool.allocated_pages == 0


def test_shared_prefix_page_is_never_rewritten(rig):
    """Two requests share a 2-page prefix: the second is token-exact off
    the first's pages; then the idle pages are overwritten with a
    sentinel on the device, and a third matching admission leaves the
    sentinel in place: its insert writes none of the shared pages."""
    model, params, eng = rig
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, 512, size=2 * PAGE).astype(np.int32)
    prompts = [
        np.concatenate([prefix, rng.integers(0, 512, size=n).astype(np.int32)])
        for n in (3, 5, 4)
    ]
    hits = eng.pool.prefix_hits
    for p in prompts[:2]:
        r = eng.submit(p, max_new_tokens=6)
        eng.run_until_idle(max_iters=100)
        np.testing.assert_array_equal(r.result(), _solo(model, params, p, 6))
    assert eng.pool.prefix_hits == hits + 2
    shared = [
        eng.pool._hash_to_page[d] for d in eng.pool.prefix_digests(prompts[2])
    ]
    assert len(shared) == 2 and 0 not in shared

    def poison(leaf):
        if leaf.ndim < 4:
            return leaf
        at = (slice(None),) * (leaf.ndim - 4) + (np.asarray(shared),)
        return leaf.at[at].set(-7.0)

    eng._cache = jax.tree_util.tree_map(poison, eng._cache)
    r = eng.submit(prompts[2], max_new_tokens=6)
    eng.run_until_idle(max_iters=100)
    assert r.done and eng.pool.prefix_hits == hits + 4
    for key, leaf in _pool_leaves(eng).items():
        assert (leaf[shared] == -7.0).all(), key
    # Leave no poisoned page behind for the tests that share the engine.
    eng.pool._hash_to_page.clear()
    eng.pool._page_hash.clear()


def test_masked_insert_touches_the_trash_page_alone(rig):
    """All of the row masked off: every page but page 0 is bit-identical
    after the insert. Some of it masked on: exactly those pool pages hold
    the row's pad-stripped pages, and the rest are as they were."""
    _, _, eng = rig
    rng = np.random.default_rng(7)
    row = jax.tree_util.tree_map(
        lambda s: jnp.asarray(
            rng.standard_normal(s.shape).astype(s.dtype)
            if len(s.shape) >= 4 else np.zeros(s.shape, s.dtype)
        ),
        eng._row_template(),
    )
    pps = eng.pages_per_slot
    table = np.zeros((pps,), np.int32)
    table[:5] = [4, 9, 2, 11, 6]
    pad = 3
    before = _pool_leaves(eng)

    def insert(mask):
        eng._cache = eng._insert(
            eng._cache, row, jnp.asarray(table), jnp.int32(pad),
            jnp.asarray(mask),
        )
        return _pool_leaves(eng)

    after = insert(np.zeros((pps,), bool))
    for key in before:
        np.testing.assert_array_equal(after[key][1:], before[key][1:])
    mask = np.zeros((pps,), bool)
    mask[[0, 2, 3]] = True
    after = insert(mask)
    rows = dict(eng._cache_leaf_items(row))
    written = table[mask]
    untouched = np.setdiff1d(np.arange(1, eng.n_pages), written)
    for key in before:
        np.testing.assert_array_equal(
            after[key][untouched], before[key][untouched]
        )
        r = np.asarray(rows[key])  # (..., 1, n_ctx, H, D)
        r = np.roll(np.take(r, 0, axis=r.ndim - 4), -pad, axis=r.ndim - 4)
        lead = r.shape[: r.ndim - 3]
        pages = np.moveaxis(
            r.reshape(lead + (pps, PAGE) + r.shape[-2:]), len(lead), 0
        )
        np.testing.assert_array_equal(after[key][written], pages[mask])


def test_export_import_round_trips_a_page_set(rig):
    """prefill_export -> store -> import: the imported pool pages are the
    exported pages bit for bit, the admission runs no prefill, and the
    tokens equal solo generate()."""
    model, params, eng = rig
    base = eng.compile_stats()
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 512, size=2 * PAGE + 3).astype(np.int32)
    pset = eng.prefill_export(prompt)
    key = eng.ship(prompt)
    prefills = eng._prefill_calls
    h = eng.submit(prompt, max_new_tokens=5, kv_key=key)
    eng.run_until_idle(max_iters=100)
    # The two full prompt pages (decode wrote into the third alone).
    pids = [
        eng.pool._hash_to_page[d] for d in eng.pool.prefix_digests(prompt)
    ]
    assert len(pids) == 2
    for j, pid in enumerate(pids):
        got = eng._read_page_host(pid)
        for leaf_key, pages in pset.pages.items():
            np.testing.assert_array_equal(got[leaf_key], pages[j])
    np.testing.assert_array_equal(h.result(), _solo(model, params, prompt, 5))
    assert eng._prefill_calls == prefills
    assert next(
        t for t in h.trace if t["phase"] == "admitted"
    )["prefilled"] == "ship"
    assert eng.compile_stats() == base
