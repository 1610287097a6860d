"""How the page sets in this directory were made: by the tree of commit
e2cc208 (PR 34, the last build whose pool leaves ended in (page_size, H, D)
and in a 20-number latent at the test width), run from that checkout's root:

    JAX_PLATFORMS=cpu python tests/data/kv_sets_pr34/make_sets.py <out-dir>

They are what an older build left in a KV store, or put on the wire: the
tests import them into the pool as it is now and compare a new export with
them byte for byte. Do not regenerate them with a later tree."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())

from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.reference import seed_key  # noqa: E402
from tpuflow.infer.serve import ServeEngine  # noqa: E402
from tpuflow.models.gpt2 import GPT2, GPT2Config  # noqa: E402

PROMPT_SEED = 35


def gpt2_set(out, scan_layers):
    model = GPT2(GPT2Config.small_test(n_ctx=64, dropout=0.0, scan_layers=scan_layers))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServeEngine(
        model, params, max_slots=2, buckets=[8, 16], decode_block=4, page_size=8,
        role="prefill", kv_store_dir=out,
    )
    prompt = np.random.default_rng(PROMPT_SEED).integers(0, 512, size=13).astype(np.int32)
    return eng.ship(prompt)


def xing4_set(out):
    fam = manifest.load_family("xing4")
    tc = fam.test_config()
    m = tc["model"]
    params = jax.jit(lambda k: fam.make_params(m, k))(seed_key(5))
    eng = ServeEngine(fam.module(m), params, buckets=[32, 48], kv_store_dir=out, **tc["serve"])
    prompt = np.random.default_rng(PROMPT_SEED).integers(1, 256, size=35).astype(np.int32)
    return eng.ship(prompt)


if __name__ == "__main__":
    root = sys.argv[1]
    for name, make in (
        ("gpt2-blocks", lambda d: gpt2_set(d, False)),
        ("gpt2-scan", lambda d: gpt2_set(d, True)),
        ("xing4", xing4_set),
    ):
        print(name, make(os.path.join(root, name)))
