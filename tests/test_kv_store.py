"""Tiered KV-page store units (tpuflow.infer.kv_store, ISSUE 19).

jax-free by construction — the module imports stdlib + numpy only, so
every edge here (atomic commit, torn/corrupt rejection, digest chains,
host-tier LRU cascade, the bounded digest→tier index, restart rescan)
pins with ZERO compiles. The engine-side exactness of what these
primitives carry lives in tests/test_serve_disagg.py.
"""

import json
import os
import zlib

import numpy as np
import pytest

from tpuflow.infer import kv_store as kvs


def _pset(prompt, ps=4, n_leaves=2, tok0=7, seed=0):
    """A KVPageSet with random page payloads shaped like cache leaves."""
    rng = np.random.default_rng(seed)
    p = np.asarray(prompt, np.int32)
    k = -(-p.size // ps)  # ceil: full pages + the partial tail page
    pages = {
        f"leaf{i}": rng.normal(size=(k, 2, ps, 3, 5)).astype(np.float32)
        for i in range(n_leaves)
    }
    return kvs.KVPageSet(
        page_size=ps,
        n_tokens=int(p.size),
        prompt=p,
        digests=kvs.chain_digests(p, ps),
        pages=pages,
        tok0=tok0,
        meta={"quant": False},
    )


# --------------------------------------------------------- digest chains
def test_chain_digests_prefix_property():
    """Entry j keys the prompt prefix through page j: chains of a prompt
    and its extension agree exactly on the shared full pages — the basis
    of suffix resume AND of PagePool/router affinity compatibility."""
    ps = 4
    base = np.arange(8, dtype=np.int32)
    ext = np.arange(13, dtype=np.int32)  # same first 8 tokens + 5 more
    other = np.arange(1, 14, dtype=np.int32)
    cb, ce = kvs.chain_digests(base, ps), kvs.chain_digests(ext, ps)
    assert len(cb) == 2 and len(ce) == 3  # FULL pages only
    assert kvs.chain_match(cb, ce) == 2
    assert kvs.chain_match(ce, kvs.chain_digests(other, ps)) == 0
    assert kvs.chain_match([], ce) == 0
    # Bit-equal to PagePool.prefix_digests / router prefix_digests.
    from tpuflow.infer.router import prefix_digests

    assert prefix_digests(ext, ps) == ce


def test_prompt_key_is_token_exact():
    a = np.arange(9, dtype=np.int32)
    assert kvs.prompt_key(a) == kvs.prompt_key(list(range(9)))
    assert kvs.prompt_key(a) != kvs.prompt_key(a[:-1])
    assert _pset(a).key == kvs.prompt_key(a)


# ------------------------------------------------------------ the store
def test_commit_load_roundtrip_bytes_exact(tmp_path):
    store = kvs.KVStore(str(tmp_path))
    pset = _pset(np.arange(11, dtype=np.int32))
    key = store.commit(pset)
    assert key == pset.key and store.contains(key)
    assert store.keys() == [key]
    got = store.load(key)
    assert got is not None
    assert got.page_size == pset.page_size
    assert got.n_tokens == 11 and got.tok0 == 7
    assert got.digests == pset.digests
    assert got.meta == {"quant": False}
    np.testing.assert_array_equal(got.prompt, pset.prompt)
    assert sorted(got.pages) == sorted(pset.pages)
    for name, arr in pset.pages.items():
        np.testing.assert_array_equal(got.pages[name], arr)
        # page_bundle is the per-page tier unit
        np.testing.assert_array_equal(
            got.page_bundle(1)[name], arr[1]
        )


def test_torn_and_corrupt_sets_never_load(tmp_path):
    """The commit protocol's whole point: every torn shape returns None
    (the serving path's local-prefill fallback), never raises, never a
    partial set."""
    store = kvs.KVStore(str(tmp_path))
    pset = _pset(np.arange(10, dtype=np.int32))
    key = store.commit(pset)

    assert store.load("no-such-key") is None

    # Blob without manifest (crash before the commit marker).
    os.remove(store._manifest(key))
    assert store.load(key) is None and not store.contains(key)
    store.commit(pset)

    # Manifest without blob (delete crashed between the two unlinks —
    # delete removes the manifest FIRST so this shape only arises from
    # external interference, and still never loads).
    os.remove(store._blob(key))
    assert store.load(key) is None
    store.commit(pset)

    # Corrupted blob byte: crc32 rejects.
    blob = store._blob(key)
    data = bytearray(open(blob, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(blob, "wb").write(bytes(data))
    assert store.load(key) is None
    store.commit(pset)

    # Truncated blob: length check rejects.
    open(blob, "wb").write(open(blob, "rb").read()[:-3])
    assert store.load(key) is None
    store.commit(pset)

    # Malformed manifest JSON.
    open(store._manifest(key), "w").write("{not json")
    assert store.load(key) is None


def test_manifest_is_the_commit_marker(tmp_path):
    """The manifest carries the blob's crc32 + byte length — recompute
    both from disk and they must agree (the marker describes exactly
    the published blob, the property a crash cannot fake)."""
    store = kvs.KVStore(str(tmp_path))
    key = store.commit(_pset(np.arange(6, dtype=np.int32)))
    manifest = json.load(open(store._manifest(key)))
    data = open(store._blob(key), "rb").read()
    assert manifest["blob_bytes"] == len(data)
    assert manifest["crc32"] == zlib.crc32(data)
    assert manifest["format"] == kvs.FORMAT_NAME


def test_gc_stage_leftovers_and_delete(tmp_path):
    store = kvs.KVStore(str(tmp_path))
    key = store.commit(_pset(np.arange(5, dtype=np.int32)))
    # A crashed writer's staging files are invisible to keys() and
    # reclaimed by the next store construction.
    stage = os.path.join(str(tmp_path), "other.npz" + kvs.STAGE_SUFFIX)
    open(stage, "wb").write(b"partial")
    assert store.keys() == [key]
    assert kvs.KVStore(str(tmp_path)).keys() == [key]
    assert not os.path.exists(stage)
    store.delete(key)
    assert store.keys() == [] and store.load(key) is None
    store.delete(key)  # idempotent


def test_trim_to_bytes_evicts_lru_first(tmp_path):
    store = kvs.KVStore(str(tmp_path))
    keys = []
    for i in range(3):
        key = store.commit(
            _pset(np.arange(i * 7, i * 7 + 9, dtype=np.int32), seed=i)
        )
        os.utime(store._manifest(key), (1000.0 + i, 1000.0 + i))
        keys.append(key)
    per = store.nbytes() // 3
    evicted = store.trim_to_bytes(2 * per + per // 2)
    assert evicted == [keys[0]]  # oldest manifest mtime first
    assert sorted(store.keys()) == sorted(keys[1:])
    assert store.trim_to_bytes(0) and store.keys() == []


# ------------------------------------------------------------- host tier
def _bundle(seed, nbytes=400):
    rng = np.random.default_rng(seed)
    return {"k": rng.normal(size=nbytes // 8).astype(np.float64)}


def test_host_tier_lru_budget_and_cascade():
    tier = kvs.HostTier(budget_bytes=1000)  # fits two 400-byte bundles
    d = [bytes([i]) * 20 for i in range(4)]
    assert tier.put(d[0], _bundle(0)) == []
    assert tier.put(d[1], _bundle(1)) == []
    assert tier.count == 2 and tier.used_bytes == 800
    # Third insert evicts the LRU (d0) as the cascade for disk.
    ev = tier.put(d[2], _bundle(2))
    assert [e[0] for e in ev] == [d[0]]
    np.testing.assert_array_equal(ev[0][1]["k"], _bundle(0)["k"])
    # A get refreshes recency: d1 touched, so d3 evicts d2.
    assert tier.get(d[1]) is not None
    ev = tier.put(d[3], _bundle(3))
    assert [e[0] for e in ev] == [d[2]]
    # pop=True frees the DRAM accounting.
    got = tier.get(d[1], pop=True)
    assert got is not None and d[1] not in tier
    assert tier.used_bytes == 400
    # An over-budget bundle cascades straight down, never cached.
    huge = {"k": np.zeros(400, np.float64)}  # 3200 > 1000
    assert tier.put(d[0], huge) == [(d[0], huge)]
    assert d[0] not in tier
    tier.drop(d[3])
    assert tier.count == 0 and tier.used_bytes == 0


# ------------------------------------------------------------ tier cache
def test_tier_cache_spill_locate_fetch_semantics(tmp_path):
    cache = kvs.TierCache(
        host_bytes=1000, disk_dir=str(tmp_path / "disk")
    )
    assert cache.armed
    d = [bytes([i]) * 20 for i in range(4)]
    assert cache.spill(d[0], _bundle(0)) == "host"
    assert cache.spill(d[1], _bundle(1)) == "host"
    # Host overflow cascades the LRU bundle down to disk.
    assert cache.spill(d[2], _bundle(2)) == "host"
    assert cache.locate(d[0]) == "disk"
    assert cache.pages_host == 2 and cache.pages_disk == 1
    assert cache.spills_host == 3 and cache.spills_disk == 1
    # Host fetch pops (the page is going back to HBM)…
    got = cache.fetch(d[1])
    assert got is not None and got[1] == "host"
    np.testing.assert_array_equal(got[0]["k"], _bundle(1)["k"])
    assert cache.locate(d[1]) is None and cache.hits_host == 1
    # …a disk fetch keeps the file (restart survival).
    got = cache.fetch(d[0])
    assert got is not None and got[1] == "disk"
    np.testing.assert_array_equal(got[0]["k"], _bundle(0)["k"])
    assert cache.locate(d[0]) == "disk" and cache.hits_disk == 1
    assert cache.fetch(b"\xee" * 20) is None  # never-spilled digest


def test_tier_cache_disk_only_restart_rescan(tmp_path):
    """kv_host_mb=0 + a disk dir spills straight to disk, and a FRESH
    TierCache over the same dir re-finds every page — the hot-prefix-
    survives-replica-restart property, at the unit level."""
    disk = str(tmp_path / "disk")
    cache = kvs.TierCache(host_bytes=0, disk_dir=disk)
    assert cache.host is None
    d = [bytes([i]) * 20 for i in range(3)]
    for i in range(3):
        assert cache.spill(d[i], _bundle(i)) == "disk"
    reborn = kvs.TierCache(host_bytes=0, disk_dir=disk)
    assert reborn.pages_disk == 3
    for i in range(3):
        assert reborn.locate(d[i]) == "disk"
        got = reborn.fetch(d[i])
        assert got is not None and got[1] == "disk"
        np.testing.assert_array_equal(got[0]["k"], _bundle(i)["k"])


def test_tier_cache_corrupt_disk_page_drops_cleanly(tmp_path):
    cache = kvs.TierCache(host_bytes=0, disk_dir=str(tmp_path / "d"))
    d = b"\x05" * 20
    assert cache.spill(d, _bundle(5)) == "disk"
    blob = cache.disk._blob(d.hex())
    data = bytearray(open(blob, "rb").read())
    data[-4] ^= 0xFF
    open(blob, "wb").write(bytes(data))
    # Fetch rejects the corrupt page, deletes it, forgets the index
    # entry — the caller prefills; nothing is served from bad bytes.
    assert cache.fetch(d) is None
    assert cache.locate(d) is None
    assert not cache.disk.contains(d.hex())


def test_tier_cache_index_is_bounded(tmp_path):
    """THE ISSUE 19 bugfix pin: the digest→tier index is an LRU bounded
    by index_max. Overflow drops the OLDEST entries; a dropped host
    entry frees its DRAM bundle, a dropped disk entry keeps its file
    (rescan re-finds it)."""
    disk = str(tmp_path / "disk")
    cache = kvs.TierCache(
        host_bytes=10**9, disk_dir=disk, index_max=3
    )
    d = [bytes([i]) * 20 for i in range(5)]
    for i in range(5):
        cache.spill(d[i], _bundle(i))
    assert len(cache._index) == 3
    assert cache.locate(d[0]) is None and cache.locate(d[1]) is None
    assert cache.pages_host == 3  # dropped host bundles freed DRAM
    for i in (2, 3, 4):
        assert cache.locate(d[i]) == "host"
    # Disk entries aged out of the index keep their files.
    cache2 = kvs.TierCache(host_bytes=0, disk_dir=disk, index_max=2)
    for i in range(5):
        cache2.spill(d[i], _bundle(i))
    assert len(cache2._index) == 2
    assert kvs.TierCache(host_bytes=0, disk_dir=disk).pages_disk == 5


def test_tier_cache_disk_budget_trims(tmp_path):
    cache = kvs.TierCache(
        host_bytes=0, disk_dir=str(tmp_path / "d"),
        disk_max_bytes=1,  # pathological: every spill trims to newest
    )
    d = [bytes([i]) * 20 for i in range(3)]
    for i in range(3):
        cache.spill(d[i], _bundle(i))
    # trim_to_bytes can never get UNDER 1 byte with a page present, but
    # it must keep at most one newest entry and never corrupt state.
    assert len(cache.disk.keys()) <= 1


def test_tier_cache_unarmed_without_tiers():
    cache = kvs.TierCache(host_bytes=0, disk_dir=None)
    assert not cache.armed
    assert cache.spill(b"\x01" * 20, _bundle(1)) is None
    assert cache.locate(b"\x01" * 20) is None
    assert cache.fetch(b"\x01" * 20) is None
    assert cache.pages_host == 0 and cache.pages_disk == 0


# ------------------------------------------------- ckpt-manager sharing
def test_ckpt_manager_marker_rides_the_same_commit_helper(tmp_path):
    """ckpt/manager.py writes its commit marker through THIS module's
    atomic_write_json (one staging idiom, zero drift): the marker's
    staging suffix is ours, and a marker write is all-or-nothing."""
    from tpuflow.ckpt import manager as ckpt_manager

    assert ckpt_manager._STAGE_SUFFIX == kvs.STAGE_SUFFIX
    path = str(tmp_path / "marker.json")
    kvs.atomic_write_json(path, {"step": 3})
    assert json.load(open(path)) == {"step": 3}
    assert os.listdir(str(tmp_path)) == ["marker.json"]


# ------------------------------------------------- sets of an older build
OLD_SETS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "kv_sets_pr34"
)


@pytest.mark.parametrize(
    "name,tokens,leaves,shape",
    [
        ("gpt2-blocks", 13, 4, (2, 8, 4, 32)),  # K and V of two blocks
        ("gpt2-scan", 13, 2, (2, 2, 8, 4, 32)),  # the layer-stacked K and V
        ("xing4", 35, 1, (3, 3, 16, 20)),  # one latent leaf, 3 layers
    ],
)
def test_a_set_committed_by_an_older_build_still_loads(
    tmp_path, name, tokens, leaves, shape
):
    """The page set's format is no property of the pool's leaf: sets
    that PR 34's build committed (``data/kv_sets_pr34/make_sets.py``),
    whose pages end in ``(page_size, H, D)`` or in a bare latent, load
    crc-clean with their digest chain, and commit again to the same
    bytes."""
    import shutil

    store = kvs.KVStore(shutil.copytree(os.path.join(OLD_SETS, name), tmp_path / name))
    (key,) = store.keys()
    pset = store.load(key)
    assert pset is not None and pset.key == key
    assert pset.n_tokens == tokens == pset.prompt.size
    assert pset.digests == kvs.chain_digests(pset.prompt, pset.page_size)
    assert [a.shape for a in pset.pages.values()] == [shape] * leaves
    with open(os.path.join(store.root, key + kvs.BLOB_SUFFIX), "rb") as f:
        blob = f.read()
    again = kvs.KVStore(str(tmp_path / "again"))
    again.commit(pset)
    with open(os.path.join(again.root, key + kvs.BLOB_SUFFIX), "rb") as f:
        assert f.read() == blob
