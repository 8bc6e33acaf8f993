"""Plain Qwen3 decoder: weights from a seed, forward pass and next-token loss.

Written from the published Qwen3 architecture (Hugging Face
``Qwen3ForCausalLM``): pre-norm blocks with RMSNorm, grouped-query
attention with a per-head RMSNorm on q and k before the rotary embedding
(rotate-half, base ``rope_theta``), a SwiGLU MLP, and an output head that
is the embedding's transpose when ``tie_word_embeddings`` is set.  Nothing
here imports the system under test.

The configuration is the benchmark's own JSON file, read with the Hugging
Face key names.  The weights are a nested dict in the layout the program
takes as its parameter tree; the layers are stacked on a leading axis.

Everything is float32, every matrix product at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# the configuration keys the model reads
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "num_hidden_layers",
        "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def sizes(cfg):
    """The model's part of a configuration file, as hashable values."""
    return {k: cfg[k] for k in KEYS}


def dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def param_shapes(cfg):
    """The weight tree's leaf shapes, as nested dicts of tuples."""
    d, h, hk, hd, ff, v, n = dims(cfg)
    layer = {
        "ln1": {"scale": (n, d)},
        "ln2": {"scale": (n, d)},
        "attn": {"wq": {"w": (n, d, h * hd)}, "wk": {"w": (n, d, hk * hd)},
                 "wv": {"w": (n, d, hk * hd)}, "wo": {"w": (n, h * hd, d)},
                 "q_norm": {"scale": (n, hd)}, "k_norm": {"scale": (n, hd)}},
        "mlp": {"gate": {"w": (n, d, ff)}, "up": {"w": (n, d, ff)},
                "down": {"w": (n, ff, d)}},
    }
    shapes = {"embed": {"emb": (v, d)}, "runs": {"0": layer},
              "final_norm": {"scale": (d,)}}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = {"w": (d, v)}
    return shapes


def _is_shape(x):
    return isinstance(x, tuple)


def init_params(key, cfg):
    """float32 weights from `key`: projections uniform in ±1/sqrt(fan in),
    the embedding normal with standard deviation 0.02, norm scales 1.
    Leaf i draws from ``fold_in(key, i)`` in the tree's flattening order."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "scale":
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "emb":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            bound = shape[-2] ** -0.5
            out.append(jax.random.uniform(k, shape, jnp.float32, -bound,
                                          bound))
    return jax.tree.unflatten(treedef, out)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, L, heads, hd): rotate-half rotary embedding at positions
    0..L-1."""
    hd, L = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _layer(x, p, cfg):
    d, h, hk, hd, ff, v, n = dims(cfg)
    eps = cfg["rms_norm_eps"]
    B, L, _ = x.shape
    a = _rms(x, p["ln1"]["scale"], eps)
    at = p["attn"]
    q = _mm(a, at["wq"]["w"]).reshape(B, L, h, hd)
    k = _mm(a, at["wk"]["w"]).reshape(B, L, hk, hd)
    vv = _mm(a, at["wv"]["w"]).reshape(B, L, hk, hd)
    q = _rope(_rms(q, at["q_norm"]["scale"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, at["k_norm"]["scale"], eps), cfg["rope_theta"])
    # query head j reads key/value head j // (h / hk)
    k = jnp.repeat(k, h // hk, axis=2)
    vv = jnp.repeat(vv, h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((L, L), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, vv, precision=HIGHEST)
    x = x + _mm(o.reshape(B, L, h * hd), at["wo"]["w"])
    m = _rms(x, p["ln2"]["scale"], eps)
    mp = p["mlp"]
    u = jax.nn.silu(_mm(m, mp["gate"]["w"])) \
        * _mm(m, mp["up"]["w"])
    return x + _mm(u, mp["down"]["w"])


def loss(params, tokens, cfg):
    """Mean next-token cross-entropy of `tokens` (B, L): positions
    0..L-2 predict tokens 1..L-1.  Each layer is rematerialised in the
    backward pass, so one layer's activations are live at a time."""
    x = params["embed"]["emb"][tokens]

    @jax.checkpoint
    def body(h, p):
        return _layer(h, p, cfg), None
    x, _ = jax.lax.scan(body, x, params["runs"]["0"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    head = params["embed"]["emb"].T if cfg["tie_word_embeddings"] \
        else params["lm_head"]["w"]
    logits = _mm(x[:, :-1], head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
