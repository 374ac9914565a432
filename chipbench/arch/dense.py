"""The dense decoder: pre-norm blocks of rotary attention and a SwiGLU
feed-forward, LayerNorm with or without affine (or RMSNorm), tied or
untied output head.

Every architecture module under `chipbench/arch/` meets the same
contract, so that the harness reaches a model only through the module
its configuration file names (`"arch"`):

* it imports nothing of the program;
* `shapes(model)` is the program's own parameter layout (tree and
  shapes), `init_params(model, key)` makes float32 parameters in that
  layout from `key` (traced: called inside a jit);
* `forward(model, params, tokens, lowp=None)` returns `(logits, aux)`:
  float32 logits over the real vocabulary, every matrix product at
  `Precision.HIGHEST` and every value rounded by `reference._round` at
  the places a lower-precision control rounds; `aux` is the
  architecture's auxiliary loss (0 for dense), which the reference loss
  weighs by the optimizer's `aux_loss`;
* `matmul_params`, `attention_flops`, `forward_flops` and
  `decode_flops` count its model FLOPs (two operations a multiply-add),
  with only the active experts counted in a routed layer.

`model` is the configuration file's `model` object, nested objects and
lists included.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import NEG, _mm, _norm, _rope, _round
from chipbench.weights import padded_vocab


def shapes(model: dict) -> dict:
    """The parameter tree's shapes: one stacked segment of `num_layers`
    blocks, tied or untied embeddings, and norm parameters only where
    the norm has them."""
    d, f = model["d_model"], model["d_ff"]
    H, K = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // H
    L, Vp = model["num_layers"], padded_vocab(model["vocab_size"])
    norm = ({} if model["norm"] == "nonparam_ln" else
            {"scale": (d,), "bias": (d,)} if model["norm"] == "layernorm"
            else {"scale": (d,)})
    embed = {"table": (Vp, d)}
    if not model["tie_embeddings"]:
        embed["unembed"] = (d, Vp)
    block = {"ln1": {k: (L,) + s for k, s in norm.items()},
             "attn": {"wq": (L, d, H, hd), "wk": (L, d, K, hd),
                      "wv": (L, d, K, hd), "wo": (L, H, hd, d)},
             "ln2": {k: (L,) + s for k, s in norm.items()},
             "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                     "w_down": (L, f, d)}}
    return {"embed": embed, "final_norm": dict(norm), "segments": [block]}


def _std(name: str, shape, num_layers: int) -> float:
    """Embeddings 0.02; projections 1/sqrt(fan-in), with the residual
    outputs (wo, w_down) scaled down by sqrt(2 * layers)."""
    if name in ("table", "unembed"):
        return 0.02
    fan_in = shape[1] * shape[2] if name == "wo" else shape[-2]
    std = 1.0 / math.sqrt(fan_in)
    if name in ("wo", "w_down"):
        std /= math.sqrt(2 * num_layers)
    return std


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(model: dict, key):
    """float32 parameters from `key`, one key per leaf in the tree's
    order: norm scales 1, biases 0, the rest truncated normal."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(model), is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shp), k in zip(flat, keys):
        name = path[-1].key
        if name == "scale":
            leaves.append(jnp.ones(shp, jnp.float32))
        elif name == "bias":
            leaves.append(jnp.zeros(shp, jnp.float32))
        else:
            std = _std(name, shp, model["num_layers"])
            leaves.append(std * jax.random.truncated_normal(
                k, -3.0, 3.0, shp, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def forward(model: dict, params, tokens, lowp=None):
    """(logits (B, S, vocab) over the real vocabulary, aux 0)."""
    kind = model["norm"]
    table = params["embed"]["table"]
    x = _round(table[tokens], lowp)
    S = tokens.shape[1]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    seg = params["segments"][0]
    for layer in range(model["num_layers"]):
        lp = jax.tree.map(lambda a: a[layer], seg)
        at = lp["attn"]
        h = _round(_norm(kind, lp["ln1"], x), lowp)
        q = _mm("bsd,dhk->bshk", h, at["wq"], lowp)
        k = _mm("bsd,dhk->bshk", h, at["wk"], lowp)
        v = _mm("bsd,dhk->bshk", h, at["wv"], lowp)
        q = _round(_rope(q, model["rope_theta"]), lowp)
        k = _round(_rope(k, model["rope_theta"]), lowp)
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = _mm("bshd,bthd->bhst", q, k, lowp) / math.sqrt(q.shape[-1])
        s = jnp.where(causal[None, None], s, NEG)
        o = _mm("bhst,bthd->bshd", jax.nn.softmax(s, -1), v, lowp)
        x = _round(x + _mm("bshk,hkd->bsd", o, at["wo"], lowp), lowp)
        h2 = _round(_norm(kind, lp["ln2"], x), lowp)
        g = _mm("bsd,df->bsf", h2, lp["mlp"]["w_gate"], lowp)
        u = _mm("bsd,df->bsf", h2, lp["mlp"]["w_up"], lowp)
        hh = _round(jax.nn.silu(g) * u, lowp)
        x = _round(x + _mm("bsf,fd->bsd", hh, lp["mlp"]["w_down"], lowp),
                   lowp)
    x = _round(_norm(kind, params["final_norm"], x), lowp)
    if model["tie_embeddings"]:
        logits = _mm("bsd,vd->bsv", x, table, lowp)
    else:
        logits = _mm("bsd,dv->bsv", x, params["embed"]["unembed"], lowp)
    return logits[..., :model["vocab_size"]], 0.0


def matmul_params(model: dict) -> int:
    """Weights that every token multiplies once in a forward pass."""
    d, f = model["d_model"], model["d_ff"]
    H, K = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // H
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f
    return model["num_layers"] * per_layer + d * model["vocab_size"]


def attention_flops(model: dict, keys_seen: int) -> int:
    """Scores and mixing, summed over the keys all queries see."""
    H = model["num_heads"]
    hd = model.get("head_dim") or model["d_model"] // H
    return model["num_layers"] * 4 * H * hd * keys_seen


def forward_flops(model: dict, rows: int, seq: int) -> int:
    """A causal forward pass over `rows` sequences of `seq` tokens."""
    causal_keys = seq * (seq + 1) // 2
    return rows * (2 * matmul_params(model) * seq
                   + attention_flops(model, causal_keys))


def decode_flops(model: dict, positions) -> int:
    """One token per lane, lane i at absolute position positions[i]."""
    return sum(2 * matmul_params(model) + attention_flops(model, int(p) + 1)
               for p in positions)
