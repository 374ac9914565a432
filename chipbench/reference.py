"""Plain reference of what the timed path computes.

Imports nothing of the program. It reads parameter trees in the
program's layout and the token arrays the timed path was given, and
computes in float32 with every matrix product at `Precision.HIGHEST`.
The model's forward pass is its architecture module's
(`chipbench/arch/<arch>.py`, passed in as `arch`), built from the
helpers here; this module adds what every architecture shares:

* the training loss (next-token cross entropy, plus 1e-4 times the
  mean squared log-partition, plus the optimizer's `aux_loss` times the
  architecture's auxiliary loss) and one AdamW step with global-norm
  clipping and the warmup-cosine learning rate;
* top-1 accuracy and logits of the forward pass;
* Jensen-Shannon drift scores of bucketed token histograms, in float64.

`lowp` names the precision a control computes in: "bf16" casts every
matrix-product operand and every activation between products to
bfloat16, as the program's own bfloat16 path does; "fp8" rounds them to
float8 after scaling each tensor by its largest magnitude (e4m3 for
values, e5m2 for gradients). A control rounds its backward pass at the
same places: the cotangent of each rounded value is rounded too, as a
training step computed in that precision would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import hashable, thaw

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _cast(x, lowp, forward: bool):
    if lowp == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if lowp == "fp8":
        # per-tensor scaling: e4m3 for values, e5m2 for gradients
        dt, top = ((jnp.float8_e4m3fn, 448.0) if forward
                   else (jnp.float8_e5m2, 57344.0))
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / s).astype(dt).astype(F32) * s
    raise ValueError(lowp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, lowp):
    return _cast(x, lowp, True)


def _rounded_fwd(x, lowp):
    return _cast(x, lowp, True), None


def _rounded_bwd(lowp, _, g):
    return (_cast(g, lowp, False),)


_rounded.defvjp(_rounded_fwd, _rounded_bwd)


def _round(x, lowp):
    """x rounded to `lowp` in the forward pass, and its gradient rounded
    to `lowp` in the backward pass; the identity for float32."""
    return x if lowp is None else _rounded(x, lowp)


def _mm(eq, a, b, lowp):
    out = jnp.einsum(eq, _round(a, lowp), _round(b, lowp), precision=HI,
                     preferred_element_type=F32)
    return _round(out, lowp)


def _norm(kind, p, x, eps=1e-5):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, theta):
    """x: (B, S, H, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(arch, model: dict, opt: dict, params, tokens, lowp=None):
    """Next-token cross entropy plus the z-loss and the weighted
    auxiliary loss, as the program trains."""
    lg, aux = arch.forward(model, params, tokens, lowp)
    lg = lg[:, :-1]
    lab = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, -1)
    picked = jnp.take_along_axis(lg, lab[..., None], -1)[..., 0]
    return (jnp.mean(lse - picked)
            + opt["z_loss"] * jnp.mean(jnp.square(lse))
            + opt.get("aux_loss", 0.0) * aux)


def adamw(opt: dict, params, grads, state):
    """One AdamW step with global-norm clipping and the program's
    warmup then cosine schedule (floor at a tenth of the peak rate)."""
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9)), grads)
    step = state["count"].astype(F32)
    count = state["count"] + 1
    warm = jnp.minimum(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    prog = jnp.clip((step - opt["warmup_steps"])
                    / max(1, opt["total_steps"] - opt["warmup_steps"]), 0, 1)
    lr = opt["learning_rate"] * warm * (0.1 + 0.9 * 0.5
                                        * (1 + jnp.cos(jnp.pi * prog)))
    b1, b2 = opt["b1"], opt["b2"]
    cf = count.astype(F32)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * g * g, state["nu"],
                      grads)
    new = jax.tree.map(
        lambda p, m, n: p - lr * ((m / (1 - b1 ** cf))
                                  / (jnp.sqrt(n / (1 - b2 ** cf))
                                     + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _train_step(arch, model_items, opt_items, lowp, params, state, tokens):
    model, opt = thaw(model_items), thaw(opt_items)
    with jax.default_matmul_precision("highest"):
        val, grads = jax.value_and_grad(
            lambda p: loss(arch, model, opt, p, tokens, lowp))(params)
        new, state = adamw(opt, params, grads, state)
    return new, state, val


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in xs]


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm} of a parameter-shaped tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def train(arch, model: dict, opt: dict, params0, batches,
          lowp=None) -> dict:
    """The first len(batches) steps from params0 and a zero AdamW state.
    Returns each step's loss, the first clipped gradient's leaf norms
    (from the first moment after one step) and the leaf norms of the
    parameters' change after the last step."""
    mi, oi = hashable(model), hashable(opt)
    state = {"mu": jax.tree.map(jnp.zeros_like, params0),
             "nu": jax.tree.map(jnp.zeros_like, params0),
             "count": jnp.zeros((), jnp.int32)}
    params, losses, grad_norms = params0, [], None
    for i, toks in enumerate(batches):
        params, state, val = _train_step(arch, mi, oi, lowp, params, state,
                                         jnp.asarray(toks, jnp.int32))
        losses.append(float(val))
        if i == 0:
            grad_norms = leaf_norms(jax.tree.map(
                lambda m: m / (1 - opt["b1"]), state["mu"]))
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, params0))
    return {"loss": losses, "grad": grad_norms, "update": delta}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _logits(arch, model_items, lowp, params, tokens):
    with jax.default_matmul_precision("highest"):
        return arch.forward(thaw(model_items), params, tokens, lowp)[0]


def logits(arch, model: dict, params, tokens, lowp=None):
    return _logits(arch, hashable(model), lowp, params,
                   jnp.asarray(tokens, jnp.int32))


def accuracy(arch, model: dict, params, tokens,
             lowp=None) -> np.ndarray:
    """Top-1 next-token accuracy of each (B, S) batch in `tokens`
    (shape (P, B, S)): the share of positions whose largest logit is the
    next token."""
    toks = np.asarray(tokens)
    P, B, S = toks.shape
    lg = np.asarray(logits(arch, model, params, toks.reshape(P * B, S),
                           lowp))
    hit = lg[:, :-1].argmax(-1) == toks.reshape(P * B, S)[:, 1:]
    return hit.reshape(P, B * (S - 1)).mean(-1)


def histograms(tokens, buckets: int, vocab: int) -> np.ndarray:
    """(N, T) tokens -> (N, buckets) float64 bucket shares: token t goes
    to bucket min(t * buckets // vocab, buckets - 1)."""
    t = np.asarray(tokens, np.int64).reshape(len(tokens), -1)
    idx = np.clip(t * buckets // vocab, 0, buckets - 1)
    h = np.zeros((t.shape[0], buckets), np.float64)
    np.add.at(h, (np.arange(t.shape[0])[:, None], idx), 1.0)
    return h / h.sum(-1, keepdims=True)


def js(p, q, eps=1e-12, dtype=np.float64) -> np.ndarray:
    """Row-wise Jensen-Shannon divergence (natural log) of the
    eps-smoothed, renormalised rows, computed in `dtype`."""
    if dtype == "bf16":
        f = jax.jit(lambda a, b: _js_jnp(a, b, eps, jnp.bfloat16))
        return np.asarray(f(jnp.asarray(p, F32), jnp.asarray(q, F32)),
                          np.float64)
    p = np.asarray(p, dtype) + eps
    q = np.asarray(q, dtype) + eps
    p, q = p / p.sum(-1, keepdims=True), q / q.sum(-1, keepdims=True)
    m = 0.5 * (p + q)
    return 0.5 * np.sum(p * np.log(p / m), -1) + \
        0.5 * np.sum(q * np.log(q / m), -1)


def _js_jnp(p, q, eps, dt):
    p = p.astype(dt) + jnp.asarray(eps, dt)
    q = q.astype(dt) + jnp.asarray(eps, dt)
    p = p / jnp.sum(p, -1, keepdims=True)
    q = q / jnp.sum(q, -1, keepdims=True)
    m = (p + q) * jnp.asarray(0.5, dt)
    out = 0.5 * jnp.sum(p * jnp.log(p / m), -1) + \
        0.5 * jnp.sum(q * jnp.log(q / m), -1)
    return out.astype(F32)
