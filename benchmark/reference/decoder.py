"""The plain reference for the GQA + RoPE + SwiGLU decoder family, with the
Mixtral top-k expert FFN: a full forward pass in ``jax.numpy`` and float32
under ``default_matmul_precision("highest")`` — no KV cache, no kernel, no
batching, one sequence at a time, layer by layer.

It follows the published architectures (Mistral-7B-v0.3, Mixtral-8x7B-v0.1 as
Hugging Face ``transformers`` computes them): pre-norm residual blocks, RMSNorm
in float32, rotary embedding on the two halves of each head, causal
grouped-query attention, SwiGLU; for Mixtral a softmax over all experts, the
top-k of it renormalised, and a per-token loop over the chosen experts.

Weights are the engine's own tree, so that the comparison is of the
computation and not of two random draws: int8 leaves ``{"q", "s"}`` are
dequantised here to float32 (``q * s`` on the axis the scale was reduced
over). One layer is dequantised at a time, so the reference fits beside the
engine on the chip. Nothing of the program's model code is imported.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dequant(w: Any, reduced_axis: int) -> jax.Array:
    """A plain or ``{"q","s"}`` weight as float32; ``s`` lacks ``reduced_axis``."""
    if isinstance(w, dict):
        return w["q"].astype(F32) * jnp.expand_dims(w["s"].astype(F32), reduced_axis)
    return w.astype(F32)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: [T, heads, hd]; position t rotates the pair (i, i + hd/2) by
    t * theta ** (-2i / hd)."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    angles = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(x: jax.Array, layer: dict[str, Any], n_heads: int,
              n_kv_heads: int, head_dim: int, theta: float) -> jax.Array:
    T = x.shape[0]
    q = (x @ dequant(layer["wq"], 0)).reshape(T, n_heads, head_dim)
    k = (x @ dequant(layer["wk"], 0)).reshape(T, n_kv_heads, head_dim)
    v = (x @ dequant(layer["wv"], 0)).reshape(T, n_kv_heads, head_dim)
    q, k = rope(q, theta), rope(k, theta)
    group = n_heads // n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(F32(head_dim))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, n_heads * head_dim) @ dequant(layer["wo"], 0)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


@jax.jit
def _one_expert(x_t: jax.Array, layer: dict[str, Any], e: jax.Array) -> jax.Array:
    """Expert ``e``'s SwiGLU on one token; only that expert is dequantised."""
    pick = lambda w: jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, e, 0, keepdims=False), w)
    # a sliced expert stack [D, F] / [F, D] has its scale on the out axis
    return swiglu(x_t[None, :], dequant(pick(layer["w1"]), 0),
                  dequant(pick(layer["w3"]), 0), dequant(pick(layer["w2"]), 0))[0]


def expert_ffn(x: jax.Array, layer: dict[str, Any], top_k: int
               ) -> tuple[jax.Array, jax.Array]:
    """Mixtral's sparse block: softmax over all experts, top-k, renormalise,
    then token by token the chosen experts' SwiGLU weighted by their gates.
    Also returns each token's routing margin: the probability by which the
    last chosen expert beat the first one left out."""
    probs = jax.nn.softmax(x @ dequant(layer["router"], 0), axis=-1)
    ranked, _ = jax.lax.top_k(probs, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    gates, chosen = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    chosen = jax.device_get(chosen)
    rows = []
    for t in range(x.shape[0]):
        row = jnp.zeros_like(x[t])
        for j in range(top_k):
            row = row + gates[t, j] * _one_expert(x[t], layer, int(chosen[t, j]))
        rows.append(row)
    return jnp.stack(rows), margin


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "theta", "eps"))
def _attention_half(x, layer, *, n_heads, n_kv_heads, head_dim, theta, eps):
    h = rms_norm(x, layer["attn_norm"], eps)
    x = x + attention(h, layer, n_heads, n_kv_heads, head_dim, theta)
    return x, rms_norm(x, layer["ffn_norm"], eps)


@jax.jit
def _dense_ffn(h, layer):
    return swiglu(h, dequant(layer["w1"], 0), dequant(layer["w3"], 0),
                  dequant(layer["w2"], 0))


@jax.jit
def _embed(embed, tokens):
    if isinstance(embed, dict):     # per-row scales
        return embed["q"][tokens].astype(F32) * embed["s"][tokens].astype(F32)[:, None]
    return embed[tokens].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    return rms_norm(x, final_norm, eps) @ dequant(head, 0)


def forward(params: dict[str, Any], config: Any, tokens: list[int],
            positions: list[int]) -> tuple[jax.Array, jax.Array | None]:
    """Logits [len(positions), vocab] of the full forward pass over ``tokens``
    (position i attends to 0..i), at the stated ``positions``; and, for a
    routed model, the smallest routing margin over the layers at each of them
    (None for a dense model).

    ``config`` needs ``n_heads, n_kv_heads, head_dim, rope_theta, norm_eps,
    n_experts, moe_top_k``; ``params`` is ``{"embed", "layers": [...],
    "final_norm", "lm_head"}`` as the engine holds it."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        margins = None
        for layer in params["layers"]:
            x, h = _attention_half(
                x, layer, n_heads=config.n_heads, n_kv_heads=config.n_kv_heads,
                head_dim=config.head_dim, theta=float(config.rope_theta),
                eps=float(config.norm_eps))
            if "router" in layer:
                out, margin = expert_ffn(h, layer, config.moe_top_k)
                x = x + out
                margins = margin if margins is None else jnp.minimum(margins, margin)
            else:
                x = x + _dense_ffn(h, layer)
        at = jnp.asarray(positions)
        logits = _head(x[at], params["final_norm"], params["lm_head"],
                       eps=float(config.norm_eps))
        return logits, None if margins is None else margins[at]
