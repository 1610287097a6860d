"""A family the harness has never heard of, for the benchmark's tests alone:
what `benchmark/families/<family>.py` has to expose for a training cell,
and nothing of GPT-2's. RMSNorm, no position table, an untied head, a
gated feed-forward, key names of its own, a depth and a vocabulary that
the configuration's file lists as reduced. It serves no cell, so it has no
`serve_gaps`, `forward_flops_per_token` or `decode_step_bytes`: a loop
that asked for one would be told so by name."""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness import reference as R

BLOCK_SCOPES = ("attn", "ffn", "head")
MODULE_SCOPES = ("Toy",)
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads", "rms_norm_eps",
          "max_position_embeddings")


class Toy(nn.Module):
    """The "program": flax modules at the default precision."""
    vocab: int
    hidden: int
    inter: int
    heads: int
    layers: int
    eps: float

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        def dense(n, name):
            return nn.Dense(n, use_bias=False, name=name)

        def norm(name):
            return nn.RMSNorm(epsilon=self.eps, name=name)

        x = nn.Embed(self.vocab, self.hidden, name="embed")(tokens)
        r, t, c = x.shape
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(self.layers):
            h = norm(f"l{i}_norm1")(x)
            q, k, v = (dense(c, f"l{i}_{n}")(h).reshape(r, t, self.heads, -1) for n in "qkv")
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(c // self.heads)
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            x = x + dense(c, f"l{i}_o")(jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(r, t, c))
            h = norm(f"l{i}_norm2")(x)
            gated = jax.nn.silu(dense(self.inter, f"l{i}_gate")(h)) * dense(self.inter, f"l{i}_up")(h)
            x = x + dense(c, f"l{i}_down")(gated)
        return dense(self.vocab, "head")(norm("norm_f")(x))


def module(m: dict):
    return Toy(m["vocab_size"], m["hidden_size"], m["intermediate_size"],
               m["num_attention_heads"], m["num_hidden_layers"], m["rms_norm_eps"])


def positions(m: dict) -> int:
    return m["max_position_embeddings"]


def vocabulary(m: dict) -> int:
    """The slice of the vocabulary held here: a smaller vocabulary."""
    return m["vocab_size"]


def check_config(cfg: dict) -> list[str]:
    bad = [f"model.{k} is not the published {cfg[k]}" for k in WIDTHS if cfg["model"][k] != cfg[k]]
    for k, v in cfg["model"].items():
        if k not in WIDTHS and cfg.get(k) != v and k not in cfg["reduced"]:
            bad.append(f"model.{k} differs from the source and is not in reduced")
    if n_params(cfg["model"]) != cfg["parameters"]:
        bad.append(f"parameters {cfg['parameters']} is not the {n_params(cfg['model'])} of the shapes")
    return bad


# ----------------------------------------------------------- the counts
def leaf_table(m: dict) -> list[tuple[tuple[str, str], tuple[int, ...], float]]:
    """(path, shape, mean) of every leaf, in a fixed order; std 0.02."""
    c, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    rows = [(("embed", "embedding"), (v, c), 0.0)]
    for i in range(m["num_hidden_layers"]):
        shapes = {"q": (c, c), "k": (c, c), "v": (c, c), "o": (c, c),
                  "gate": (c, f), "up": (c, f), "down": (f, c)}
        rows += [((f"l{i}_{n}", "kernel"), s, 0.0) for n, s in shapes.items()]
        rows += [((f"l{i}_{n}", "scale"), (c,), 1.0) for n in ("norm1", "norm2")]
    return rows + [(("norm_f", "scale"), (c,), 1.0), (("head", "kernel"), (c, v), 0.0)]


def n_params(m: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(m))


def train_flops_per_token(m: dict) -> float:
    """6 for every parameter a token is multiplied by: all but the
    embedding table, which is read by row."""
    return 6.0 * (n_params(m) - m["vocab_size"] * m["hidden_size"])


def attention_flops(m: dict, batch: int, seq: int, steps: float) -> float:
    """Causal score and value products, forward and backward."""
    per_layer = 3 * 0.5 * 4.0 * batch * seq * seq * m["hidden_size"]
    return steps * m["num_hidden_layers"] * per_layer


# ------------------------------------------------- weights from `--seed`
def make_params(m: dict, key) -> dict:
    tree: dict = {}
    for i, ((mod, leaf), shape, mean) in enumerate(leaf_table(m)):
        noise = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        tree.setdefault(mod, {})[leaf] = mean + 0.02 * noise
    return tree


def leaf_norms(tree, m: dict, *, minus_key=None, scale: float = 1.0) -> dict[str, float]:
    first = jax.jit(lambda k: make_params(m, k))(minus_key) if minus_key is not None else None
    out = {}
    for (mod, leaf), _, _ in leaf_table(m):
        a = tree[mod][leaf] - (first[mod][leaf] if first else 0.0)
        out[f"{mod}/{leaf}"] = float(jnp.linalg.norm(a.ravel())) * scale
    return out


def delta_norms(params, m: dict, seed: int) -> dict[str, float]:
    return leaf_norms(params, m, minus_key=R.seed_key(seed))


# --------------------------------------------------- the plain reference
def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w["scale"]


def forward_logits(params, tokens, m: dict, quant=None):
    """(R, T) ids -> (R, T, V) logits, float32 at `highest`."""
    q = R.QUANT[quant]

    def mm(a, w):
        return jnp.matmul(q(a), q(w["kernel"]), precision=R.HIGHEST)

    heads, eps = m["num_attention_heads"], m["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    r, t, c = x.shape
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(m["num_hidden_layers"]):
        w = {n: params[f"l{i}_{n}"] for n in ("norm1", "norm2", "q", "k", "v", "o", "gate", "up", "down")}
        h = _rms(x, w["norm1"], eps)
        qh, kh, vh = (mm(h, w[n]).reshape(r, t, heads, -1) for n in "qkv")
        s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh), precision=R.HIGHEST)
        p = jax.nn.softmax(jnp.where(causal, s / math.sqrt(c // heads), -1e30), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", q(p), q(vh), precision=R.HIGHEST)
        x = x + mm(a.reshape(r, t, c), w["o"])
        h = _rms(x, w["norm2"], eps)
        x = x + mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])
    return mm(_rms(x, params["norm_f"], eps), params["head"])


def train_reference(m: dict, opt: dict, seed: int, batches, *, quant=None,
                    rows_per_block: int = 1, fault: str | None = None) -> dict:
    def loss(params, x, y, scale):
        logp = jax.nn.log_softmax(forward_logits(params, x, m, quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, y[..., None], axis=-1)) * scale

    @jax.jit
    def block(params, g_acc, x, y, scale):
        part, g = jax.value_and_grad(loss)(params, x, y, scale)
        return part, jax.tree_util.tree_map(jnp.add, g_acc, g)

    params = jax.jit(lambda k: make_params(m, k))(R.seed_key(seed))
    out, params = R.follow_steps(
        params, opt, batches, block, lambda g: leaf_norms(g, m),
        rows_per_block=rows_per_block, fault=fault,
    )
    out["dparam_norms"] = delta_norms(params, m, seed)
    return out
