"""The plain reference of the ``starcoder2-7b`` configuration: the
decoder's forward pass in straightforward `jax.numpy` and float32 at
the highest matmul precision, with no kernels, no cache and no
batching.  Imports nothing of the program; it reads the parameter tree
by the names the program serves it under.

Follows the StarCoder2 block (arXiv:2402.19173: pre-norm decoder, GQA
with rotary positions, sliding-window causal attention, a 4x MLP with
tanh-gelu), with the departures the configuration's file lists under
``assumed``: RMSNorm without bias (epsilon 1e-6) for LayerNorm, no
biases, an output head of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def init_params(shapes, key):
    """Seeded weights for the tree of shapes the program's model
    declares, in the float32 the program stores: norm scales 1, every
    other leaf normal with standard deviation 1/sqrt(fan_in) (the input
    axis is the first; for the embedding, the model width).  Made on
    the device, to be called under one `jax.jit`."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
            continue
        fan_in = leaf.shape[-1] if "embedding" in name else leaf.shape[0]
        out.append(jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                     leaf.dtype) * (fan_in ** -0.5))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), out)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def _rope(x, theta):
    """Rotate ``x`` (heads, S, dh) by position: split-half pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _identity(x):
    return x


def _block(p, x, *, heads, kv_heads, window, theta, quant):
    """One decoder block on ``x`` (S, dim).  ``quant`` rounds the two
    operands of every weight matmul (identity in the reference, fp8 in
    the control)."""
    s = x.shape[0]
    attn = p["GQASelfAttention_0"]
    y = _rms_norm(x, p["RMSNorm_0"]["scale"])
    proj = lambda name: jnp.einsum(  # noqa: E731
        "sd,dhk->hsk", quant(y), quant(attn[name]["kernel"]))
    q, k, v = proj("q_proj"), proj("k_proj"), proj("v_proj")
    q, k = _rope(q, theta), _rope(k, theta)
    group = heads // kv_heads
    pos = jnp.arange(s)
    keep = (pos[None, :] <= pos[:, None]) & (
        pos[None, :] > pos[:, None] - window)
    outs = []
    for h in range(kv_heads):  # one KV head's group at a time
        qh = q[h * group:(h + 1) * group]
        scores = jnp.einsum("gsk,tk->gst", qh, k[h]) * (q.shape[-1] ** -0.5)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        outs.append(jnp.einsum("gst,tk->gsk",
                               jax.nn.softmax(scores, axis=-1), v[h]))
    out = jnp.concatenate(outs, 0).transpose(1, 0, 2).reshape(s, -1)
    x = x + quant(out) @ quant(attn["o_proj"]["kernel"])
    y = _rms_norm(x, p["RMSNorm_1"]["scale"])
    mlp = p["MLP_0"]
    h = jax.nn.gelu(quant(y) @ quant(mlp["Dense_0"]["kernel"]),
                    approximate=True)
    return x + quant(h) @ quant(mlp["Dense_1"]["kernel"])


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "theta", "rows", "low_precision"))
def _forward(params, tokens, first, *, heads, kv_heads, window, theta,
             rows, low_precision):
    quant = fp8_round if low_precision else _identity
    x = params["Embed_0"]["embedding"][tokens]
    depth = sum(1 for name in params if name.startswith("TransformerBlock_"))
    for i in range(depth):
        x = _block(params[f"TransformerBlock_{i}"], x, heads=heads,
                   kv_heads=kv_heads, window=window, theta=theta,
                   quant=quant)
    x = _rms_norm(x, params["RMSNorm_0"]["scale"])
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    return quant(x) @ quant(params["Dense_0"]["kernel"])


def served_logits(params, config: dict, prompt, served, *, pad_to: int,
                  rows: int, low_precision: bool = False) -> np.ndarray:
    """Float32 logits at the positions that predict the ``served``
    tokens of one request: one pass over prompt + served tokens (causal,
    so the zero tail up to ``pad_to`` reaches nothing).  ``rows`` >=
    len(served) is the static number of positions computed; the rows
    past the served ones are cut off."""
    seq = np.zeros((pad_to,), np.int32)
    real = list(prompt) + list(served[:-1])
    seq[:len(real)] = real
    with jax.default_matmul_precision("highest"):
        logits = _forward(
            params, jnp.asarray(seq), len(prompt) - 1,
            heads=int(config["num_attention_heads"]),
            kv_heads=int(config["num_key_value_heads"]),
            window=int(config["sliding_window"]),
            theta=float(config["rope_theta"]), rows=rows,
            low_precision=low_precision)
    return np.asarray(logits, np.float64)[:len(served)]


def widest_gap(logits: np.ndarray, tokens) -> float:
    """The widest gap by which a token's logit lies below the best."""
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(logits.max(axis=1) - picked))


def fp8_round(x):
    """The control's precision: float8 e4m3 under one scale per tensor,
    the step below the bf16 the configuration states."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
