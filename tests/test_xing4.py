"""Xing4.0 (`tpuflow/models/xing4.py`) against the plain reference of its
family file (`benchmark/families/xing4.py`: full heads, no cache, a dense
sum over the experts) on seeded weights at the family's `test` width, in
float32 on both sides, so what is left is the order of float32 sums: 2e-5
of a logit of size 0.7 covers it (read: 1e-7 to 2e-6). Through the engine the
comparison is the benchmark's own, the widest gap by which a served token
lies below the reference's best (0 where the tokens agree)."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.reference import seed_key  # noqa: E402
from tpuflow.infer.serve import ServeEngine  # noqa: E402
from tpuflow.models import xing4 as X  # noqa: E402
from tpuflow.ops import grouped_matmul  # noqa: E402

FAM = manifest.load_family("xing4")
TOL = 2e-5  # float32 sums in another order (see the module docstring)
SEED = 5


def _config(**over):
    m = dict(FAM.test_config()["model"])
    m.update(over)
    return m


@pytest.fixture(scope="module")
def with_mtp():
    m = _config(n_mtp=1)
    params = jax.jit(lambda k: FAM.make_params(m, k))(seed_key(SEED))
    return m, FAM.module(m), params


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 24), 1, 256)


@pytest.fixture(scope="module")
def forward(with_mtp, tokens):
    m, model, params = with_mtp
    logits, mtp = jax.jit(lambda p, t: model.apply({"params": p}, t, mtp=True))(params, tokens)
    ref_fn = jax.jit(lambda p, t: FAM.forward_logits(p, t, m, mtp=True))
    ref = [ref_fn(params, tokens[b]) for b in range(2)]
    return logits, mtp, ref


@pytest.mark.parametrize("row", [0, 1])
@pytest.mark.parametrize("which", ["logits", "mtp"])
def test_forward_equals_the_reference(forward, which, row):
    """The whole forward pass, and the multi-token module's logits."""
    logits, mtp, ref = forward
    got = (logits if which == "logits" else mtp)[row]
    want = ref[row][0 if which == "logits" else 1]
    assert float(jnp.abs(want).max()) > 0.3  # the comparison sees something
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_float8_control_is_far_from_the_program(with_mtp, tokens, forward):
    m, _, params = with_mtp
    low = jax.jit(lambda p, t: FAM.forward_logits(p, t, m, quant="fp8"))(params, tokens[0])
    assert float(jnp.abs(low - forward[0][0]).max()) > 100 * TOL


@pytest.mark.parametrize("pads", [None, (0, 3)])
def test_the_absorbed_path_equals_the_expanded_path(with_mtp, tokens, forward, pads):
    """A fresh chunk attends with full heads (`_expanded`), every later
    step in the latent space over the cached rows (`_absorbed`): prefill 8
    then 16 single steps give the plain forward's logits. With left pads
    the rows are compared with a forward of the unpadded tokens."""
    _, model, params = with_mtp
    toks, want = tokens, forward[0]
    pad_lens = None
    if pads is not None:
        pad_lens = jnp.asarray(pads, jnp.int32)
        plain = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens[1:, 3:])
        toks = tokens.at[1, :3].set(0)
        want = want.at[1, 3:].set(plain[0])
    first = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, decode=True, prefill=True, pad_lens=pad_lens, mutable=["cache"]))
    step = jax.jit(lambda p, c, t: model.apply(
        {"params": p, "cache": c}, t, decode=True, pad_lens=pad_lens, mutable=["cache"]))
    lg, vs = first(params, toks[:, :8])
    out, cache = [lg], vs["cache"]
    for i in range(8, 24):
        lg, vs = step(params, cache, toks[:, i:i + 1])
        out.append(lg)
        cache = vs["cache"]
    got = jnp.concatenate(out, axis=1)
    lo = 3 if pads is not None else 0
    assert float(jnp.abs(got[0] - want[0]).max()) < TOL
    assert float(jnp.abs(got[1, lo:] - want[1, lo:]).max()) < TOL


@pytest.mark.parametrize("lanes", ["zeros", "garbage"])
def test_the_paged_decode_equals_the_rows_decode_whatever_the_pad_lanes_hold(
    with_mtp, tokens, lanes
):
    """The pool keeps a token's 20 numbers (576 at the cell's size) in
    128 lanes (640): prefill 8 through the page table, then 16 single
    steps, give the logits of the same calls over contiguous rows, also
    with the pad lanes of every page filled with garbage after the
    prefill (they are sliced off the gathered rows)."""
    m, model, params = with_mtp
    ps, held = 8, m["kv_lora_rank"] + m["qk_rope_head_dim"]
    paged = model.clone(config=dataclasses.replace(model.config, kv_pages=9, kv_page_size=ps))
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)  # 32 positions a row
    call = dict(decode=True, mutable=["cache"])
    rows_first = jax.jit(lambda p, t: model.apply({"params": p}, t, prefill=True, **call))
    rows_step = jax.jit(lambda p, c, t: model.apply({"params": p, "cache": c}, t, **call))
    pool_first = jax.jit(lambda p, t: paged.apply(
        {"params": p}, t, slot_index=jnp.zeros((2,), jnp.int32), page_table=table, **call))
    pool_step = jax.jit(lambda p, c, t, at: paged.apply(
        {"params": p, "cache": c}, t, slot_index=at, page_table=table, **call))
    want, vs = rows_first(params, tokens[:, :8])
    got, pvs = pool_first(params, tokens[:, :8])
    rows, pool = vs["cache"], pvs["cache"]
    assert rows["latent"].shape[-1] == held
    assert pool["latent"].shape == (3, 9, ps, 128)
    assert not np.asarray(pool["latent"][..., held:]).any()  # written as zeros
    if lanes == "garbage":
        pool = {**pool, "latent": pool["latent"].at[..., held:].set(3e4)}
    assert float(jnp.abs(got - want).max()) < TOL
    for i in range(8, 24):
        want, vs = rows_step(params, rows, tokens[:, i:i + 1])
        got, pvs = pool_step(params, pool, tokens[:, i:i + 1], jnp.full((2,), i, jnp.int32))
        rows, pool = vs["cache"], pvs["cache"]
        assert float(jnp.abs(got - want).max()) < TOL, i


# ------------------------------------------------------------- the router
def _router_case(case):
    m = _config()
    cfg = FAM.module(m).config
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 8)) * 0.3, jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)
    if case == "bias":
        bias = jnp.asarray(rng.normal(size=(8,)) * 0.5, jnp.float32)
    elif case == "ties":  # experts 2 and 5 score alike on every token
        w = w.at[:, 5].set(w[:, 2])
    elif case == "one_expert_pair":  # every token picks experts 6 and 1
        bias = bias.at[6].set(50.0).at[1].set(40.0)
    return m, cfg, x, w, bias


@pytest.mark.parametrize("case", ["plain", "bias", "ties", "one_expert_pair"])
def test_router_choices_and_weights(case):
    """The choice goes by `s + b`, the weight by `s` alone, normalised over
    the chosen and scaled; a tie goes to the lower index on both sides."""
    m, cfg, x, w, bias = _router_case(case)
    idx, wts = X.route(x, w, bias, cfg)
    dense = np.zeros((40, 8), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(wts), axis=1)
    want = np.asarray(FAM.router(x, {"router": w, "router_bias": bias}, m))
    assert (np.count_nonzero(want, axis=1) == 2).all()
    np.testing.assert_allclose(dense, want, atol=1e-6)
    np.testing.assert_allclose(dense.sum(1), 2.0, atol=1e-5)  # routed_scaling_factor
    if case == "one_expert_pair":
        assert set(np.asarray(idx).ravel().tolist()) == {1, 6}
    if case == "bias":  # the bias moved some choice, and no weight
        plain, _ = X.route(x, w, jnp.zeros_like(bias), cfg)
        assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(idx))).any()


@pytest.mark.parametrize("impl", ["ragged_dot", "gmm"])
@pytest.mark.parametrize("case", ["plain", "one_expert_pair", "dead_rows"])
def test_no_token_is_dropped_whatever_the_load(case, impl, monkeypatch):
    """The grouped products against a dense sum over all experts; with
    every token on one pair of experts those two get all 40 tokens; rows
    marked dead are routed nowhere and come back nought. Through XLA's
    grouped product, which `auto` takes here, and through the Pallas one it
    takes on the chip (interpret mode: 80 pairs padded to its row tile,
    groups of no rows, rows past the last group)."""
    monkeypatch.setattr(
        X, "grouped_dot", functools.partial(grouped_matmul.grouped_dot, impl=impl, interpret=True)
    )
    m, cfg, x, w, bias = _router_case("plain" if case == "dead_rows" else case)
    rng = np.random.default_rng(8)
    gate_up = jnp.asarray(rng.normal(size=(8, 64, 64)) * 0.1, jnp.float32)
    down = jnp.asarray(rng.normal(size=(8, 32, 64)) * 0.1, jnp.float32)
    valid = jnp.ones((40,), bool)
    if case == "dead_rows":
        valid = valid.at[10:25].set(False)
    idx, wts = X.route(x, w, bias, cfg)
    y, sizes = X.routed_experts(x, idx, wts, valid, gate_up[None], down[None], jnp.float32)
    dense = FAM.router(x, {"router": w, "router_bias": bias}, m)
    want = sum(
        dense[:, e:e + 1] * FAM._ffn(x, gate_up[e], down[e], lambda a: a) for e in range(8)
    ) * valid[:, None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    assert int(sizes.sum()) == 2 * int(valid.sum())
    if case == "one_expert_pair":
        assert sizes.tolist() == [0, 40, 0, 0, 0, 0, 40, 0]
    if case == "dead_rows":
        assert float(jnp.abs(y[10:25]).max()) == 0.0


@pytest.mark.parametrize("backend, want", [("tpu", "gmm"), ("cpu", "ragged_dot")])
def test_auto_takes_the_pallas_grouped_product_on_a_tpu_alone(backend, want):
    assert grouped_matmul.resolve_grouped_impl("auto", backend=backend) == want
    assert grouped_matmul.resolve_grouped_impl("ragged_dot", backend=backend) == "ragged_dot"


@pytest.mark.parametrize("m, k, n, itemsize, groups, want", [
    (32, 3584, 2048, 2, 64, (32, 1792, 1024)),    # gate and up of a published expert, 8 rows live
    (64, 3584, 2048, 2, 64, (32, 1792, 1024)),    # 16 rows: two pairs an expert want no wider tile
    (96, 1024, 3584, 2, 64, (32, 1024, 1792)),    # down, 24 rows
    (128, 1024, 3584, 2, 64, (32, 1024, 1792)),   # down, 32 rows
    (4608, 3584, 2048, 2, 64, (256, 1792, 1024)),  # a prefill of 1,152 tokens: 72 pairs an expert
    (6144, 3584, 2048, 2, 64, (256, 1792, 1024)),  # and of 1,536
    (1024, 3584, 2048, 2, 64, (64, 1792, 1024)),  # 16 pairs an expert: between the two
    (21, 64, 32, 4, 8, (24, 64, 32)),             # the test width: whole matrices, rows in eights
])
def test_the_grouped_kernels_blocks_follow_from_the_shapes(m, k, n, itemsize, groups, want):
    tiling = grouped_matmul.gmm_tiling(m, k, n, itemsize, groups)
    assert tiling == want
    assert tiling[1] * tiling[2] * itemsize <= 4 << 20 and k % tiling[1] == 0 and n % tiling[2] == 0


@pytest.mark.parametrize("tile", [8, 16])
def test_a_group_split_by_a_row_tiles_edge_is_whole(tile, monkeypatch):
    """With tiles narrower than the rows (a decode block of 16 rows or more
    at the published width) a group's rows lie in two tiles and the kernel
    visits it twice: the product is XLA's all the same (interpret mode)."""
    monkeypatch.setattr(grouped_matmul, "_MIN_TILE_M", tile)
    monkeypatch.setattr(grouped_matmul, "_RIDGE_ROWS", 1)
    rng = np.random.default_rng(11)
    sizes = jnp.asarray([0, 5, 0, 9, 3, 0, 11, 6] + [0] * 8, jnp.int32)  # 34 of 40 rows
    lhs = jnp.asarray(rng.normal(size=(40, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(16, 128, 128)) * 0.1, jnp.float32)
    assert grouped_matmul.gmm_tiling(40, 128, 128, 4, 8)[0] == tile
    got = grouped_matmul.grouped_dot(lhs, rhs, sizes, groups=8, impl="gmm", interpret=True)
    want = grouped_matmul.grouped_dot(lhs, rhs, sizes, impl="ragged_dot")
    np.testing.assert_allclose(np.asarray(got[:34]), np.asarray(want[:34]), atol=1e-4)


# ------------------------------------------------------ hyper-connections
@pytest.mark.parametrize("alpha", [1.0, 3.0])
def test_h_res_is_doubly_stochastic_and_the_coefficients_vary(alpha):
    m = _config()
    cfg = FAM.module(m).config
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.normal(size=(1, 12, 4, 64)), jnp.float32)
    phi = jnp.asarray(rng.normal(size=(256, 24)) * 0.05, jnp.float32)
    al = jnp.full((3,), alpha, jnp.float32)
    b = jnp.asarray(rng.normal(size=(24,)) * 0.3, jnp.float32)
    pre, post, res = X.hc_coefficients(xs, phi, al, b, cfg)
    # Columns are normalised last; 20 alternations bring the rows to 1e-5
    # at the size the weights are drawn at, and to 5e-3 at three times it
    # (read 2.4e-3: exp of a wider spread converges more slowly).
    np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.sum(-1)), 1.0, atol=1e-5 if alpha == 1.0 else 5e-3)
    want = FAM.hc_coefficients(xs[0], phi, al, b, m)
    for got, ref in zip((pre, post, res), want):
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref), atol=1e-5)
    # An alpha of 0 would make the mechanism a constant: it is not.
    assert float(jnp.std(res[0, :, 0, 0])) > 0.02 * alpha
    assert float(jnp.std(pre[0, :, 0])) > 0.02 * alpha


def test_a_layer_with_large_alpha_equals_the_reference(with_mtp, tokens):
    m, model, params = with_mtp
    big = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 3.0 if "alpha" in jax.tree_util.keystr(path) else a, params
    )
    fwd = jax.jit(lambda p, t: model.apply({"params": p}, t))
    got = fwd(big, tokens[:1])[0]
    want = jax.jit(lambda p, t: FAM.forward_logits(p, t, m))(big, tokens[0])
    assert float(jnp.abs(got - want).max()) < TOL
    moved = fwd(params, tokens[:1])[0]
    assert float(jnp.abs(got - moved).max()) > 100 * TOL  # alpha matters


def test_yarn_frequencies_keep_the_fast_dimensions_and_scale_the_slow():
    inv = np.asarray(X.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0))
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:5], plain[:5], rtol=1e-6)
    np.testing.assert_allclose(inv[-5:], plain[-5:] / 64.0, rtol=1e-6)
    assert (np.diff(inv) < 0).all()
    assert X.yarn_mscale(64.0, 1.0) == pytest.approx(1.4159, abs=1e-4)


# --------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def served():
    """Three requests on one system prompt through the paged latent cache."""
    tc = FAM.test_config()
    m = tc["model"]
    model = FAM.module(m)
    params = jax.jit(lambda k: FAM.make_params(m, k))(seed_key(SEED))
    eng = ServeEngine(model, params, buckets=[32, 48], **tc["serve"])
    warm = eng.warmup()
    rng = np.random.default_rng(0)
    system = rng.integers(1, 256, 32)
    prompts = [
        np.concatenate([system, rng.integers(1, 256, n)]).astype(np.int32) for n in (5, 9, 3)
    ]
    first = eng.submit(prompts[0], max_new_tokens=20)
    eng.run_until_idle()
    rest = [eng.submit(p, max_new_tokens=20) for p in prompts[1:]]
    again = eng.submit(prompts[0], max_new_tokens=20)
    eng.run_until_idle()
    return {"m": m, "model": model, "params": params, "engine": eng, "warm": warm,
            "prompts": prompts, "handles": [first] + rest, "again": again}


def test_the_cache_is_one_latent_vector_a_token(served):
    eng, m = served["engine"], served["m"]
    shapes = jax.tree_util.tree_map(lambda a: a.shape, eng._cache)
    # 16 + 4 numbers a token, kept in whole 128-lane rows (576 in 640 at the cell's size)
    assert shapes["latent"] == (3, eng.n_pages, eng.page_size, 128)
    assert m["kv_lora_rank"] + m["qk_rope_head_dim"] == 20
    assert eng.ledger.pool_token_widths == {"['latent']": (20, 128)}
    assert eng.ledger.pool_pad_fraction == pytest.approx(108 / 128)
    # the page axis of each leaf, asked of the model (the axis that grows with kv_pages)
    assert eng._page_axis == {"['latent']": 1, "['cache_index']": None}


def test_served_tokens_equal_the_reference_at_every_position(served):
    """Prefill then decode through the pool: every served token is the
    reference's best at its position (teacher-forced full forward)."""
    samples = [
        (p, np.asarray(h.tokens, np.int32))
        for p, h in zip(served["prompts"], served["handles"])
    ]
    assert all(len(t) == 20 for _, t in samples)
    gaps = FAM.serve_gaps(served["m"], SEED, samples)
    assert gaps["tokens"] == 60 and gaps["widest_gap"] <= TOL


def test_a_shared_system_prompt_reuses_latent_pages_and_tokens(served):
    eng = served["engine"]
    assert eng.pool.prefix_hits >= 3 * 2  # two full pages of the system prompt, three times
    assert served["again"].tokens == served["handles"][0].tokens
    cold = ServeEngine(
        served["model"], served["params"], buckets=[32, 48],
        **{**FAM.test_config()["serve"], "prefix_cache": False},
    )
    alone = cold.submit(served["prompts"][1], max_new_tokens=20)
    cold.run_until_idle()
    assert alone.tokens == served["handles"][1].tokens


def test_compile_stats_do_not_grow_after_warm_up(served):
    eng = served["engine"]
    assert eng.compile_stats() == served["warm"]
    assert served["warm"]["decode"] == len(eng.decode_shapes)


def test_the_decode_blocks_count_the_experts_their_live_rows_touched(served):
    led = served["engine"].ledger.snapshot()
    steps, touched = led["model_steps"], led["step_sum"]["experts_touched"]
    assert steps > 0 and 2 * 2 <= touched / steps <= 2 * 8  # 2 layers, 2..8 experts
    assert 1.0 <= led["step_max"]["expert_max_load"] <= 8.0


def test_export_and_import_move_latent_pages(served, tmp_path):
    """The disaggregated path on a latent leaf: pages one engine ships
    admit the prompt on another without a prefill, same tokens."""
    tc = FAM.test_config()
    prompt = served["prompts"][2]
    make = lambda: ServeEngine(  # noqa: E731
        served["model"], served["params"], buckets=[32, 48],
        kv_store_dir=str(tmp_path), **tc["serve"],
    )
    src, dst = make(), make()
    pset = src.prefill_export(prompt)
    assert pset.n_pages == -(-prompt.size // src.page_size)
    (pages,) = pset.pages.values()
    assert pages.shape == (pset.n_pages, 3, src.page_size, 20)
    h = dst.submit(prompt, max_new_tokens=20, kv_key=src.ship(prompt))
    dst.run_until_idle()
    assert dst._prefill_calls == 0
    assert h.tokens == served["handles"][2].tokens


def test_a_page_set_of_the_older_build_imports_and_a_new_export_equals_it(served, tmp_path):
    """`tests/data/kv_sets_pr34/xing4` was committed by the build whose
    pool leaf ended in the bare latent (20 numbers here, 576 at the
    cell's size). It loads, admits its prompt into the padded pool with
    no prefill and the reference's tokens follow; what this build
    exports for the same prompt is the same bytes, pad lanes stripped."""
    import shutil

    from tpuflow.infer.kv_store import KVStore

    store = str(tmp_path / "store")
    shutil.copytree(os.path.join(ROOT, "tests", "data", "kv_sets_pr34", "xing4"), store)
    (key,) = KVStore(store).keys()
    old = KVStore(store).load(key)
    assert {a.shape for a in old.pages.values()} == {(3, 3, 16, 20)}
    eng = ServeEngine(
        served["model"], served["params"], buckets=[32, 48], kv_store_dir=store,
        **FAM.test_config()["serve"],
    )
    new = eng.prefill_export(old.prompt)
    assert new.tok0 == old.tok0 and new.digests == old.digests
    for leaf, pages in old.pages.items():
        np.testing.assert_array_equal(new.pages[leaf], pages)
    prefills = eng._prefill_calls
    h = eng.submit(old.prompt, max_new_tokens=12, kv_key=key)
    eng.run_until_idle()
    assert eng._prefill_calls == prefills
    gaps = FAM.serve_gaps(served["m"], SEED, [(old.prompt, np.asarray(h.tokens, np.int32))])
    assert gaps["tokens"] == 12 and gaps["widest_gap"] <= TOL


# ---------------------------------------------------------------- the kernel
@pytest.mark.parametrize("v_dim,want", [(128, "flash"), (64, "xla")])
def test_auto_refuses_the_flash_pair_for_values_of_another_width(v_dim, want):
    """Latent attention's expanded heads: 192-wide queries and keys do not
    tile (XLA, as heads of 96); and where they would, values of another
    width still take XLA."""
    from tpuflow.ops.attention import resolve_attention_impl

    assert resolve_attention_impl(
        "auto", (1, 1024, 32, 128), 1024, backend="tpu", v_dim=v_dim) == want
    assert resolve_attention_impl(
        "auto", (1, 1024, 32, 192), 1024, backend="tpu", v_dim=128) == "xla"


def test_the_served_configuration_leaves_the_multi_token_module_out(with_mtp):
    _, model, params = with_mtp
    served = model.clone(config=dataclasses.replace(model.config, n_mtp=0))
    shapes = jax.eval_shape(
        lambda k: served.init(k, jnp.zeros((1, 4), jnp.int32)), jax.random.PRNGKey(0)
    )["params"]
    assert not [k for k in shapes if k.startswith("mtp")]
    assert [k for k in params if k.startswith("mtp")]
