"""The plain reference: GPT-2 in straightforward `jax.numpy`, float32 at
`highest` matmul precision, with its loss, gradients and AdamW. No kernels,
no cache, no batching tricks; it imports nothing of the program.

Memory: the training reference follows the program's first steps at the
cell's own batch. It runs row block by row block and layer by layer, adding
each layer's gradient into one accumulator in place, so that parameters,
both Adam moments and one gradient tree are all that lives on the chip
beside one block's activations.

`quant` turns the same code into the control: every matrix product's
operands are rounded to float8 on the way in (e4m3, per-tensor scale) and
their gradients on the way back (e5m2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights as W

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0
F8_E5M2_MAX = 57344.0


def _identity(a):
    return a


def _round_to(a, dtype, top):
    """Round to a float8 type and back, with a per-tensor scale."""
    s = jnp.max(jnp.abs(a)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_round(a):
    """An operand as a float8 matrix product sees it: e4m3 on the way in,
    and its gradient e5m2 on the way back (the usual float8 recipe)."""
    return _round_to(a, jnp.float8_e4m3fn, F8_MAX)


fp8_round.defvjp(
    lambda a: (fp8_round(a), None),
    lambda _, g: (_round_to(g, jnp.float8_e5m2, F8_E5M2_MAX),),
)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def bf16_round(a):
    """An operand rounded to bfloat16, and its gradient on the way back: the
    precision the configurations state. No control (it is not below the
    stated precision) but a witness: `tools/first_steps.py` reads how far
    rounding of that size alone moves each number compared."""
    return _bf16(a)


bf16_round.defvjp(lambda a: (_bf16(a), None), lambda _, g: (_bf16(g),))


QUANT = {None: _identity, "fp8": fp8_round, "bf16": bf16_round}


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def layer(x, lp, n_head: int, eps: float, q):
    """One pre-LN block on (R, T, C)."""
    r, t, c = x.shape
    d = c // n_head
    h = _ln(x, lp["ln_1"], eps)
    qkv = jnp.matmul(q(h), q(lp["c_attn"]["kernel"]), precision=HIGHEST)
    qkv = qkv + lp["c_attn"]["bias"]
    qh, kh, vh = (a.reshape(r, t, n_head, d) for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh), precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", q(p), q(vh), precision=HIGHEST)
    a = a.reshape(r, t, c)
    x = x + jnp.matmul(q(a), q(lp["c_proj"]["kernel"]), precision=HIGHEST) + lp["c_proj"]["bias"]
    h = _ln(x, lp["ln_2"], eps)
    h = jnp.matmul(q(h), q(lp["mlp_fc"]["kernel"]), precision=HIGHEST) + lp["mlp_fc"]["bias"]
    h = _gelu(h)
    h = jnp.matmul(q(h), q(lp["mlp_proj"]["kernel"]), precision=HIGHEST) + lp["mlp_proj"]["bias"]
    return x + h


def _embed(params, tokens):
    t = tokens.shape[1]
    return params["wte"][tokens] + params["wpe"][:t][None]


def _head_logits(x, ln_f, wte, eps, q):
    x = _ln(x, ln_f, eps)
    return jnp.matmul(q(x), q(wte).T, precision=HIGHEST)


def forward_logits(params, tokens, m: dict, quant=None):
    """(R, T) token ids -> (R, T, V) logits."""
    q = QUANT[quant]
    eps = m.get("ln_eps", 1e-5)

    def body(x, lp):
        return layer(x, lp, m["n_head"], eps, q), None

    x, _ = lax.scan(body, _embed(params, tokens), params["h"]["block"])
    return _head_logits(x, params["ln_f"], params["wte"], eps, q)


def _take_layer(tree, l):
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False), tree
    )


def _block_grad(params, g_acc, x_tok, y_tok, scale, *, m, quant):
    """Loss (times `scale`) of one block of rows, and its gradient added
    into `g_acc` layer by layer."""
    q = QUANT[quant]
    eps = m.get("ln_eps", 1e-5)
    n_head, n_layer = m["n_head"], m["n_layer"]
    layers = params["h"]["block"]
    fn = functools.partial(layer, n_head=n_head, eps=eps, q=q)

    def fwd(x, lp):
        return fn(x, lp), x

    x_last, xs = lax.scan(fwd, _embed(params, x_tok), layers)

    def head(x, ln_f, wte):
        logits = _head_logits(x, ln_f, wte, eps, q)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y_tok[..., None], axis=-1)[..., 0]
        return jnp.sum(nll) * scale

    loss, (dx, d_lnf, d_wte) = jax.value_and_grad(head, argnums=(0, 1, 2))(
        x_last, params["ln_f"], params["wte"]
    )

    def bwd(i, carry):
        dx, g = carry
        l = n_layer - 1 - i
        x_in = lax.dynamic_index_in_dim(xs, l, 0, keepdims=False)
        _, vjp = jax.vjp(fn, x_in, _take_layer(layers, l))
        dx_in, d_lp = vjp(dx)
        g = jax.tree_util.tree_map(
            lambda acc, d: lax.dynamic_update_index_in_dim(
                acc, lax.dynamic_index_in_dim(acc, l, 0, keepdims=False) + d, l, 0
            ),
            g, d_lp,
        )
        return dx_in, g

    dx0, g_layers = lax.fori_loop(0, n_layer, bwd, (dx, g_acc["h"]["block"]))
    t = x_tok.shape[1]
    g_wte = (g_acc["wte"] + d_wte).at[x_tok.reshape(-1)].add(
        dx0.reshape(-1, dx0.shape[-1])
    )
    g_wpe = g_acc["wpe"].at[:t].add(jnp.sum(dx0, axis=0))
    g_lnf = jax.tree_util.tree_map(jnp.add, g_acc["ln_f"], d_lnf)
    return loss, {
        "wte": g_wte, "wpe": g_wpe, "h": {"block": g_layers}, "ln_f": g_lnf,
    }


def _adamw(p, mu, nu, g, t, *, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    """optax.adamw's arithmetic: Adam's scaled moments, decoupled weight
    decay added to the update, then times -lr."""
    def one(p, mu, nu, g):
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * g * g
        u = (mu / (1.0 - b1**t)) / (jnp.sqrt(nu / (1.0 - b2**t)) + eps)
        return p - lr * (u + wd * p), mu, nu

    out = jax.tree_util.tree_map(one, p, mu, nu, g)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple)
    )
    return pick(0), pick(1), pick(2)


def delta_norms(params, m: dict, seed: int) -> dict[str, float]:
    """Per leaf, the norm of (params - the seed's initial leaf)."""
    return W.leaf_norms(params, m, minus_key=W.seed_key(seed))


def train_reference(m: dict, opt: dict, seed: int, batches, *, quant=None,
                    rows_per_block: int = 1, fault: str | None = None) -> dict:
    """Follow `len(batches)` AdamW steps from the seed's weights.

    `batches` is a list of (x, y) int arrays (B, T). Returns each step's
    loss, the per-leaf norm of the first gradient and the per-leaf norm of
    the parameters' change after the last step. `fault="half_batch"` leaves
    out the second half of every batch and takes the mean over the rest.
    """
    lr, wd = float(opt["learning_rate"]), float(opt.get("weight_decay", 0.0))
    key = W.seed_key(seed)
    params = jax.jit(lambda k: W.make_params(m, k))(key)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    mu, nu = zeros(), zeros()
    block = jax.jit(
        functools.partial(_block_grad, m=m, quant=quant), donate_argnums=(1,)
    )
    update = jax.jit(
        functools.partial(_adamw, lr=lr, wd=wd), donate_argnums=(0, 1, 2)
    )
    losses, grad_norms = [], None
    for step, (x, y) in enumerate(batches, start=1):
        x, y = np.asarray(x), np.asarray(y)
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        rows = x.shape[0]
        scale = jnp.float32(1.0 / (rows * x.shape[1]))
        g = zeros()
        loss = 0.0
        for r0 in range(0, rows, rows_per_block):
            part, g = block(
                params, g, jnp.asarray(x[r0:r0 + rows_per_block]),
                jnp.asarray(y[r0:r0 + rows_per_block]), scale,
            )
            loss += float(part)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = W.leaf_norms(g, m)
        params, mu, nu = update(params, mu, nu, g, jnp.float32(step))
    dparam = delta_norms(params, m, seed)
    del params, mu, nu
    return {"losses": losses, "grad_norms": grad_norms, "dparam_norms": dparam}


def serve_gaps(m: dict, seed: int, samples, *, quant=None) -> dict:
    """Teacher-forced check of served tokens.

    `samples` is a list of (prompt ids, served ids). For every served token
    the reference's logits at that position give the gap by which the
    served token lies below the reference's best. With `quant`, the gap of
    the token the lower precision puts first is read beside it (the
    control)."""
    n_ctx = m["n_ctx"]
    params = jax.jit(lambda k: W.make_params(m, k))(W.seed_key(seed))

    @jax.jit
    def gaps(params, tokens):
        logits = forward_logits(params, tokens[None], m)[0]
        best = jnp.max(logits, axis=-1)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        served = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        if quant is None:
            return best - served, best - served
        low = jnp.argmax(forward_logits(params, tokens[None], m, quant)[0], axis=-1)
        return best - served, best - jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]

    widest = widest_low = 0.0
    n_tokens = 0
    for prompt, served in samples:
        prompt, served = np.asarray(prompt, np.int32), np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served])[:n_ctx]
        padded = np.zeros((n_ctx,), np.int32)
        padded[: seq.size] = seq
        g_served, g_low = gaps(params, jnp.asarray(padded))
        # logits at position i predict token i+1: the served tokens sit at
        # positions len(prompt)-1 ... len(seq)-2.
        lo, hi = prompt.size - 1, seq.size - 1
        if hi > lo:
            widest = max(widest, float(jnp.max(g_served[lo:hi])))
            widest_low = max(widest_low, float(jnp.max(g_low[lo:hi])))
            n_tokens += hi - lo
    del params
    return {"widest_gap": widest, "widest_gap_low": widest_low, "tokens": n_tokens}
