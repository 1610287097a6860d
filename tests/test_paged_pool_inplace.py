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
  (ISSUE 33) gathers ``(rows, pages, page_size, H * D)`` and, at less
  than the full width, no gather or product in it has the extent of
  ``n_ctx``: the guard that keeps the full read from coming back.
- **The pool's shape, and what the chip makes of it** (ISSUE 35): every
  paged leaf of both model families ends in ``(pages, page_size, width)``
  with ``width`` a whole number of 128-lane rows, and the decode block,
  the verify block and the insert, lowered and compiled here for a
  described v5e at the benchmark cells' pool shapes, take the pool in
  row-major order and hold no ``copy`` of it. With ``(page_size, H, D)``
  or a 576-number latent as the tail the chip put the page axis on the
  lanes and every block and insert copied the pool there and back.
- **Exactness over both cache layouts** (one pool per block, and the
  layer-stacked pool the benchmark's cell serves): engine tokens equal
  solo ``generate()``; a shared prefix page is never rewritten by a
  matching admission; a masked-off insert touches the trash page alone;
  export -> import of a page set round-trips bit for bit.
"""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402
from tpuflow.infer import generate  # noqa: E402
from tpuflow.infer.serve import ServeEngine  # noqa: E402
from tpuflow.models.gpt2 import GPT2, GPT2Config  # noqa: E402
from tpuflow.ops import paged_pool  # noqa: E402

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
    """Host copies of the pool's K/V leaves with the page axis first:
    ``(pages, ..., page_size, H * D)``."""
    return {
        key: np.moveaxis(np.asarray(leaf), eng._page_axis[key], 0)
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
    page_size, H * D)`` and, at less than the full width, no gather or
    product touches an operand of more than two axes with an extent of
    ``n_ctx`` positions. Sizes chosen so that no model width (4 heads
    of 32, 128 wide, 512 in the feed-forward and the vocabulary)
    equals ``n_ctx`` (256) or the 192-position read; the 128-position
    read shares its extent with the model's width, so that rung is told
    by its gathers alone."""
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
        if eqn.primitive.name == "gather" and len(shapes[-1]) == 4:
            gathered.append(shapes[-1])
        # The position table (n_ctx, n_embd) is looked up by a gather
        # too: its operand is two-dimensional and no part of the read.
        extents.update(d for sh in shapes if len(sh) > 2 for d in sh)
    layers = 1 if scan_layers else 3  # the layer scan's body is one block
    assert gathered == [(rows, pages, PAGE, 4 * 32)] * (2 * layers)
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

    def poison(path, leaf):
        axis = eng._page_axis[jax.tree_util.keystr(path)]
        if axis is None:
            return leaf
        return leaf.at[(slice(None),) * axis + (np.asarray(shared),)].set(-7.0)

    eng._cache = jax.tree_util.tree_map_with_path(poison, eng._cache)
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
        pages = np.moveaxis(  # a token's (H, D) is one vector in the pool
            r.reshape(lead + (pps, PAGE, -1)), len(lead), 0
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
            # The set's format is the row's: (page_size, H, D) a page,
            # whatever the pool's leaf ends in.
            assert pages.shape[-3:] == (PAGE, 4, 32)
            np.testing.assert_array_equal(got[leaf_key], pages[j])
    np.testing.assert_array_equal(h.result(), _solo(model, params, prompt, 5))
    assert eng._prefill_calls == prefills
    assert next(
        t for t in h.trace if t["phase"] == "admitted"
    )["prefilled"] == "ship"
    assert eng.compile_stats() == base


# ------------------------- the pool's shape, and what the chip makes of it
CELLS = {"gpt2": "serve-medium-chat", "xing4": "serve-xing4-reason",
         "sdar": "serve-sdar-blockgen"}


def _param_shapes(model):
    """Shapes in the weights' place: enough for an engine that is traced
    and lowered, never run."""
    return jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )


def _cell_engine(family, **serve):
    """The cell's engine at its own widths, slots and pool, cut to two
    layers (the layer axis is the pool's major one) and holding shapes
    for weights: it is traced and lowered, never run."""
    cell = manifest.load_cell(CELLS[family])
    cfg, fam = cell["config"], cell["family"]
    m = dict(cfg["model"], n_layer=2)
    if "first_k_dense" in m:
        m["first_k_dense"] = 1  # one dense layer and one routed
    model = fam.module(m)
    return ServeEngine(
        model, _param_shapes(model), buckets=list(cell["traffic"]["buckets"])[:1],
        **{**cfg["serve"], **serve},
    )


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a v5e 2x2 that is described, not attached. Asked for
    inside a fixture: only the worker that runs this file loads the
    TPU's library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "family,program",
    [("gpt2", "decode"), ("gpt2", "insert"), ("gpt2", "verify"),
     ("xing4", "decode"), ("xing4", "insert"),
     ("sdar", "decode"), ("sdar", "insert")],
)
def test_the_chip_keeps_the_pool_page_major_and_copies_none_of_it(
    one_chip, family, program
):
    """Compiled for the chip at the cell's pool shape
    (``f32[2,897,16,1024]`` twice, ``bf16[2,8193,16,640]``,
    ``bf16[2,8193,16,512]`` twice): the pool enters in row-major order,
    pages major, and no ``copy`` in the program has an operand of its
    size. The third family's decode program is the block-diffusion one
    (``_denoise_fn``): every pass of it, the commit among them, writes a
    block's keys and values into the pool by index."""
    eng = _cell_engine(family, **({"speculative": 2} if program == "verify" else {}))
    pool = [leaf for _, leaf in eng._cache_leaf_items(eng._cache)]

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    if program == "decode":
        lowered = eng._decode.lower(
            described(eng.params), described(eng._cache),
            *described(eng._decode_warm_args(*eng.decode_shapes[0])),
        )
    elif program == "verify":
        lowered = eng._verify.lower(
            described(eng.params), described(eng._cache),
            *described((
                jnp.asarray(eng._page_table), eng._tok,
                jnp.zeros((eng.max_slots, eng.spec_draft), jnp.int32),
                eng._lengths, eng._pads, eng._remaining, eng._live, eng._eos,
            )),
        )
    else:
        lowered = eng._insert.lower(
            described(eng._cache), described(eng._row_template()),
            *described(eng._insert_warm_args()),
        )
    compiled = lowered.compile()
    text = compiled.as_text()
    entry = re.search(r"entry_computation_layout=\{(.*)\}\n", text).group(1)
    for leaf in pool:
        assert leaf.shape[-1] % paged_pool.LANES == 0
        dims = ",".join(map(str, leaf.shape))
        layouts = set(re.findall(r"\[" + dims + r"\]\{([\d,]*)", entry))
        major_to_minor = ",".join(str(i) for i in reversed(range(leaf.ndim)))
        assert layouts == {major_to_minor}, (
            f"the chip lays {leaf.dtype}[{dims}] out as {layouts}: not pages "
            "major, so every program that indexes it copies it"
        )
    n_pool = min(math.prod(leaf.shape) for leaf in pool)
    copies = [
        line.strip()[:200]
        for line in text.splitlines()
        if (m := re.match(r"\s*(?:ROOT )?%?copy[\w.\-]* = \w+\[([\d,]+)\]", line))
        and math.prod(map(int, m.group(1).split(","))) >= n_pool
    ]
    assert not copies, copies
    # A copy of the pool under any other name would need room for it
    # (the verify block reads every slot's whole row: its gathers alone
    # are the size of this two-layer pool).
    if program != "verify":
        pool_bytes = min(leaf.size * leaf.dtype.itemsize for leaf in pool)
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.slow
def test_the_chip_runs_two_attention_kernels_a_layer_and_keeps_o_once(
    one_chip, monkeypatch
):
    """The training cell's step (``gpt2-large``, batch 8 x 1,024, AdamW)
    compiled for the described chip (ISSUE 38; here because one file alone
    may load the TPU's library, 14 s): a rematerialised block keeps the
    attention kernels' o and lse, so the program holds two Mosaic calls,
    the forward and the one backward, and not the forward a second time;
    o is stacked once, as ``bf16[36,8,1024,1280]`` rows and never as
    ``(36, 8, 1024, 20, 64)``, which the chip pads; and
    ``memory_analysis()`` reads at most 1.6 GB of temporaries over the
    6.82 GB it read before the stack was kept (8.38: it counts what the
    forward scan stacks twice, so 0.78 GB of o and lse read as 1.56; the
    buffer assignment itself grows by 0.78, PERF.md section 6)."""
    from tpuflow.ops import flash_attention
    from tpuflow.train import TrainState, make_optimizer, make_train_step

    cell = manifest.load_cell("train-large-steady")
    cfg, tr = cell["config"], cell["traffic"]
    # Off the TPU `auto` is XLA's attention and the kernels are interpreted:
    # ask for the kernels by name and have them lowered for the chip.
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    model = cell["family"].module(dict(cfg["model"], attn_impl="flash"))
    tx = make_optimizer(**cfg["optimizer"])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    state = jax.eval_shape(
        lambda: TrainState.create(
            apply_fn=model.apply, params=_param_shapes(model), tx=tx
        )
    )
    tokens = jax.ShapeDtypeStruct(
        (int(tr["batch_size"]), int(tr["seq_len"])), jnp.int32
    )
    compiled = make_train_step().lower(
        described(state), described({"x": tokens, "y": tokens}),
        described(jax.eval_shape(lambda: jax.random.PRNGKey(1))),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    m = cfg["model"]
    wide = (m["n_layer"], tokens.shape[0], tokens.shape[1], m["n_embd"])
    heads = wide[:3] + (m["n_head"], m["n_embd"] // m["n_head"])
    assert "[" + ",".join(map(str, wide)) + "]" in text
    assert "[" + ",".join(map(str, heads)) + "]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 6.82e9 + 1.6e9


@pytest.mark.parametrize(
    "family,layout",
    [("gpt2", "blocks"), ("gpt2", "scan"), ("gpt2-96", "blocks"), ("xing4", "scan"),
     ("sdar", "scan")],
)
def test_every_paged_leaf_ends_in_whole_lane_rows(family, layout):
    """On the CPU, the model asked alone: a pool leaf is ``(..., pages,
    page_size, width)``, width what a token holds rounded up to whole
    128-lane rows (3 heads of 32 pad 96 to 128; a latent of 20, 576 at
    the cell's size, pads to 128, 640 there; grouped keys and values of
    32, 512 at the cell's size, pad to 128, not at all there)."""
    if family == "xing4":
        fam = manifest.load_family("xing4")
        m = fam.test_config()["model"]
        model, held = fam.module(m), m["kv_lora_rank"] + m["qk_rope_head_dim"]
    elif family == "sdar":  # keys and values of 2 groups of 16: 32 in 128
        fam = manifest.load_family("sdar")
        m = fam.test_config()["model"]
        model, held = fam.module(m), m["n_kv_head"] * m["head_dim"]
    else:
        heads = 3 if family == "gpt2-96" else 4
        model = GPT2(GPT2Config.small_test(
            n_ctx=32, n_head=heads, n_embd=32 * heads, dropout=0.0,
            scan_layers=layout == "scan",
        ))
        held = 32 * heads
    eng = ServeEngine(
        model, _param_shapes(model), max_slots=2, buckets=[16], page_size=PAGE,
        n_pages=9,
    )
    leaves = eng._cache_leaf_items(eng._cache)
    assert leaves
    for key, leaf in leaves:
        assert leaf.shape[eng._page_axis[key]:] == (
            9, PAGE, paged_pool.token_width(held)
        ), key
        assert leaf.shape[-1] % 128 == 0 and leaf.shape[-1] - held < 128
    widths = eng.ledger.pool_token_widths
    assert set(widths) == {key for key, _ in leaves}
    assert all(w == (held, paged_pool.token_width(held)) for w in widths.values())
    assert eng.ledger.snapshot()["pool_pad_fraction"] == pytest.approx(
        1 - held / paged_pool.token_width(held)
    )


def test_a_width_that_pads_serves_the_same_tokens():
    """3 heads of 32: a token's 96 numbers lie in 128 lanes of the pool.
    Engine tokens equal solo ``generate()``, garbage in the pad lanes
    changes nothing (they are sliced off the gathered rows), and an
    exported page has the row's tail, (page_size, H, D)."""
    model = GPT2(GPT2Config.small_test(
        n_ctx=32, n_head=3, n_embd=96, dropout=0.0, scan_layers=True
    ))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[16], decode_block=4, page_size=PAGE
    )
    assert eng.ledger.pool_pad_fraction == pytest.approx(0.25)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, size=n).astype(np.int32) for n in (11, 5)]
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    eng.step()  # both admitted: their prompts' pages are in the pool
    assert all(r.tokens for r in reqs)
    eng._cache = jax.tree_util.tree_map(
        lambda leaf: leaf.at[..., 96:].set(1e9) if leaf.ndim == 4 else leaf,
        eng._cache,
    )
    eng.run_until_idle(max_iters=100)
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(r.result(), _solo(model, params, p, 7))
    pset = eng.prefill_export(prompts[0])
    assert {a.shape for a in pset.pages.values()} == {(2, 2, PAGE, 3, 32)}
    pid = eng.pool._hash_to_page[eng.pool.prefix_digests(prompts[0])[0]]
    for key, page in eng._read_page_host(pid).items():
        np.testing.assert_array_equal(page, pset.pages[key][0])
