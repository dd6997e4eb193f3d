"""Plain reference of the decoder whose layers are window or full attention
over dense or expert FFNs (the ``afmoe`` layer stack), for the comparison that
decides ``correct`` and for the CPU tests. Plain ``jax.numpy`` in float32
under ``default_matmul_precision("highest")``: the whole sequence at once, no
cache, no ring, no kernel, no batching, nothing imported from
``mcp_context_forge_tpu``; the engine's own weight tree, int8 leaves
``{"q", "s"}`` dequantised a layer (an expert) at a time.

**The layer equations** (``config.json`` of Trinity-Mini: 32 layers, hidden
2048, 32 query heads and 4 kv heads of 128, ``layer_types`` three
``sliding_attention`` then one ``full_attention``, ``sliding_window`` 2048, 2
dense layers of width 6144 then 128 experts of width 1024 top-8 beside one
shared expert, ``score_func`` sigmoid, ``route_norm``, ``route_scale`` 2.826,
``rope_theta`` 10000, ``rms_norm_eps`` 1e-5, ``mup_enabled``, vocabulary
200192 untied). ``RMS_x`` is an RMSNorm with its own weight. For tokens
``t_i`` at positions ``p_i = i``:

1. ``x = E[t] * sqrt(D)`` (``mup_enabled``).
2. ``a = RMS_in(x)``; ``q = RMS_q((a Wq) as [T, H, hd])``, ``k = RMS_k((a Wk)
   as [T, KV, hd])`` over ``head_dim``, one weight vector all heads share;
   ``v = a Wv``.
3. A WINDOW layer rotates q and k at the true positions (theta 10000, all
   ``hd`` dims, the pair (i, i + hd/2)); a FULL layer does not rotate.
4. ``s_ij = q_i . k_j / sqrt(hd)``; key j is visible to query i iff ``j <=
   i`` and, in a window layer, also ``i - j < W`` (a query sees W keys, its
   own included). Query head g reads kv head ``g // (H / KV)``.
5. ``x = x + RMS_post_attn(((softmax(s) v) as [T, H hd] * sigmoid(a Wg)) Wo)``:
   the output gate, one value a head dimension.
6. ``m = RMS_pre_mlp(x)``. A dense layer: ``f = (silu(m W1) * (m W3)) W2``. An
   expert layer: ``s = sigmoid(m Wr)`` [E] float32; the ``top_k`` experts by
   ``s + b`` (``b`` the correction bias: it chooses, it does not weigh);
   ``w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale``; ``f = sum_k w_k
   Expert_k(m) + Shared(m)``, every expert a SwiGLU.
7. ``x = x + RMS_post_mlp(f)``. After the last layer ``RMS_f``, then the head.
   ``logits_i`` predict token i + 1.

Deliberately NOT the program's formulation: attention is an explicit ``[T, T]``
mask a layer kind over the whole sequence (in blocks of queries, so that a few
thousand tokens fit), where the program keeps a ring of pages a window layer
and compares positions relative to the first page a window touches; the
expert FFN loops over the experts, dequantising one at a time, where the
program scans stacked weights under gate masks or runs a row-block kernel.

``forward(..., variant=...)`` computes a named WRONG program instead, for the
readings a tolerance is set between and for the tests that must tell them
apart: ``"no_window"`` (window layers see every earlier key), ``"rotate_full"``
(full layers rotated like window layers), ``"no_gate"`` (the output gate left
out), ``"int8_activations"`` (the input of every matmul rounded to 255 levels
a token, exact accumulation: the gentlest precision below the one stated).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
VARIANTS = (None, "no_window", "rotate_full", "no_gate", "int8_activations")


def dequant(w, reduced_axis: int = 0):
    """A plain or ``{"q","s"}`` weight as float32; ``s`` lacks ``reduced_axis``."""
    if isinstance(w, dict):
        return w["q"].astype(F32) * jnp.expand_dims(w["s"].astype(F32), reduced_axis)
    return jnp.asarray(w, F32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(weight, F32)


def _rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1: the pair (i, i + hd/2) turns by
    t * theta ** (-2i / hd)."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    angles = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _rounded(x, on: bool):
    """x with each row rounded to 255 levels of its largest magnitude (int8
    activations, symmetric, a scale a token), or x itself."""
    if not on:
        return x
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale


@functools.partial(jax.jit, static_argnames=("cfg", "window", "rotate", "gate",
                                             "int8"))
def _attention_half(x, layer, *, cfg, window, rotate, gate, int8):
    """x [T, D] -> (x after the attention sublayer, the FFN's normed input).
    ``window``: None for a layer that sees every earlier key."""
    H, KV, hd, eps, theta = cfg
    T = x.shape[0]
    a = _rounded(_rms(x, layer["attn_norm"], eps), int8)
    q = _rms((a @ dequant(layer["wq"])).reshape(T, H, hd), layer["q_norm"], eps)
    k = _rms((a @ dequant(layer["wk"])).reshape(T, KV, hd), layer["k_norm"], eps)
    v = (a @ dequant(layer["wv"])).reshape(T, KV, hd)
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    q, k, v = _rounded(q, int8), _rounded(k, int8), _rounded(v, int8)
    key_at = jnp.arange(T)[None, :]
    blocks = []
    for start in range(0, T, QUERY_BLOCK):
        query_at = jnp.arange(start, min(start + QUERY_BLOCK, T))[:, None]
        seen = key_at <= query_at                            # [query, key]
        if window is not None:
            seen &= query_at - key_at < window
        scores = jnp.einsum("thd,shd->hts", q[start:start + QUERY_BLOCK], k) \
            / jnp.sqrt(F32(hd))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", _rounded(probs, int8), v))
    out = jnp.concatenate(blocks).reshape(T, H * hd)
    if gate:
        out = out * jax.nn.sigmoid(a @ dequant(layer["wg"]))
    mixed = _rounded(out, int8) @ dequant(layer["wo"])
    x = x + _rms(mixed, layer["post_attn_norm"], eps)
    return x, _rms(x, layer["ffn_norm"], eps)


def _swiglu(m, w1, w3, w2, int8):
    return _rounded(jax.nn.silu(m @ w1) * (m @ w3), int8) @ w2


@functools.partial(jax.jit, static_argnames=("int8",))
def _dense(m, layer, *, int8):
    m = _rounded(m, int8)
    return _swiglu(m, dequant(layer["w1"]), dequant(layer["w3"]),
                   dequant(layer["w2"]), int8)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "int8"))
def _experts(m, layer, *, top_k, scale, int8):
    """m [T, D] -> (routed + shared FFN output [T, D], each token's routing
    margin: the corrected score by which its last chosen expert beat the
    first one left out)."""
    scores = jax.nn.sigmoid(m @ jnp.asarray(layer["router"], F32))    # [T, E]
    ranked, chosen = jax.lax.top_k(scores + jnp.asarray(layer["router_bias"], F32),
                                   top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    kept = jnp.take_along_axis(scores, chosen, axis=1)
    kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20) * scale
    E = scores.shape[-1]
    gates = jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], chosen].set(kept)
    m = _rounded(m, int8)

    def one(e, out):
        pick = lambda w: jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, 0, keepdims=False), w)
        # a sliced expert stack [D, F] / [F, D] has its scale on the out axis
        w1, w3, w2 = (dequant(pick(layer[name])) for name in ("w1", "w3", "w2"))
        return out + jax.lax.dynamic_index_in_dim(gates, e, 1) \
            * _swiglu(m, w1, w3, w2, int8)

    routed = jax.lax.fori_loop(0, E, one, jnp.zeros_like(m))
    shared = _swiglu(m, dequant(layer["shared_w1"]), dequant(layer["shared_w3"]),
                     dequant(layer["shared_w2"]), int8)
    return routed + shared, margin


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(embed, tokens, *, multiplier):
    if isinstance(embed, dict):     # per-row scales
        rows = embed["q"][tokens].astype(F32) * embed["s"][tokens].astype(F32)[:, None]
    else:
        rows = embed[tokens].astype(F32)
    return rows * multiplier


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, final_norm, head, *, eps, int8):
    return _rounded(_rms(x, final_norm, eps), int8) @ dequant(head)


def forward(params, config, tokens, positions, variant: str | None = None):
    """Logits [len(positions), vocab] of ONE full forward pass over ``tokens``
    (at positions 0..) at the stated ``positions``, and the smallest routing
    margin over the expert layers at each of them.

    ``config`` needs ``dim, n_heads, n_kv_heads, head_dim, rope_theta,
    norm_eps, moe_top_k, sliding_window, global_attn_every,
    routed_scaling_factor``; ``params`` is the engine's tree (an expert layer
    is one that holds a ``router``)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    cfg = (config.n_heads, config.n_kv_heads, config.head_dim,
           float(config.norm_eps), float(config.rope_theta))
    every, eps = int(config.global_attn_every), float(config.norm_eps)
    int8 = variant == "int8_activations"
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32),
                   multiplier=float(config.dim) ** 0.5)
        margins = None
        for i, layer in enumerate(params["layers"]):
            full = i % every == every - 1
            sees_all = full or variant == "no_window"
            x, m = _attention_half(
                x, layer, cfg=cfg,
                window=None if sees_all else int(config.sliding_window),
                rotate=not full or variant == "rotate_full",
                gate=variant != "no_gate", int8=int8)
            if "router" in layer:
                f, margin = _experts(m, layer, top_k=int(config.moe_top_k),
                                     scale=float(config.routed_scaling_factor),
                                     int8=int8)
                margins = margin if margins is None else jnp.minimum(margins, margin)
            else:
                f = _dense(m, layer, int8=int8)
            x = x + _rms(f, layer["post_ffn_norm"], eps)
        at = jnp.asarray(positions)
        logits = _head(x[at], params["final_norm"], params["lm_head"], eps=eps,
                       int8=int8)
        return logits, None if margins is None else margins[at]
