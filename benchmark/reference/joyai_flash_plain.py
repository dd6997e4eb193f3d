"""Plain reference of the latent-attention / shared + routed expert decoder
WITHOUT a selector and WITH its multi-token-prediction block (JoyAI-LLM-Flash;
the DeepSeek-V3 layer and the V3 report's section 2.2 block), for the
comparison that decides ``correct``. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernel, no batching,
nothing imported from ``mcp_context_forge_tpu``; the engine's own weight tree,
each weight cast to float32 where it is used, the held experts' share and the
vocabulary slice as the program's.

Deliberately NOT the program's formulation:

- attention in the NON-absorbed form: per head, ``k_nope`` and ``v`` are
  expanded from the latent ``c`` through ``W_kvb`` and scores are
  ``q_nope . k_nope + q_rope . k_rope`` over every earlier position (there is
  no selector: causality is the only mask);
- experts one after the other over the ones held, each over every token,
  weighted by a dense ``[T, E]`` gate matrix; the router in float32 like
  everything else, top-k by a full sort (``n_group`` 1: no group limiting);
- the block over the WHOLE sequence at once, from the main model's hidden
  states of the whole sequence: no cache of its own, no verify step.

With ``h_i`` the last layer's output at position ``i`` BEFORE the final norm
and ``t_{i+1}`` the token that follows::

    x_i     = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] . W_eh
    y_i     = Layer_mtp(x_i)        # causal over x_0..x_i, rotary position i
    draft_i = Head(RMSNorm_s(y_i))  # a guess for t_{i+2}

``forward`` returns for each position ``p`` asked for the main logits at ``p``
BESIDE ``draft_{p-1}``: two guesses for the same token ``t_{p+1}``, one row
``[2 V]``, so that the harness's one comparison of one shape judges both.

A layer is ONE jitted program, queries in blocks of ``QUERY_BLOCK`` so that a
check prompt of 4608 tokens fits beside the engine. ``margins``: per position
the smallest gap between the 8th and 9th corrected router score over the
layers that produced its row (the main layers at ``p``, the block at ``p-1``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
NEG = -1e30


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _inv_freq(cfg) -> np.ndarray:
    """Plain rotary frequencies from ``rope_theta``: the model has no rope
    scaling."""
    dim = cfg.qk_rope_head_dim
    return (1.0 / cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                     / dim)).astype(np.float32)


def _rope(x, pos, inv_freq):
    """x [T, ..., d]: halves pair up (x[i] with x[i + d/2])."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _attention(layer, cfg, h, pos):
    """Causal latent attention, non-absorbed: -> [T, D]."""
    T = h.shape[0]
    H, dn, dr, dv, dc = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    inv_freq = _inv_freq(cfg)
    c_q = _rms(h @ _f32(layer["wq_a"]), layer["q_norm"], cfg.norm_eps)
    q = (c_q @ _f32(layer["wq_b"])).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], pos, inv_freq)
    kv_a = h @ _f32(layer["wkv_a"])
    c = _rms(kv_a[:, :dc], layer["kv_norm"], cfg.norm_eps)
    k_r = _rope(kv_a[:, dc:], pos, inv_freq)                        # [T, dr]
    kv_b = (c @ _f32(layer["wkv_b"])).reshape(T, H, dn + dv)
    k_n, v = kv_b[..., :dn], kv_b[..., dn:]
    scale = (dn + dr) ** -0.5

    def block(rows):
        """One block of queries (row numbers; past T: padding, masked out)."""
        at = jnp.minimum(rows, T - 1)
        causal = (pos[None, :] <= pos[at][:, None]) & (rows < T)[:, None]
        scores = (jnp.einsum("thd,shd->hts", q_n[at], k_n)
                  + jnp.einsum("thd,sd->hts", q_r[at], k_r)) * scale
        probs = jax.nn.softmax(jnp.where(causal[None], scores, NEG), axis=-1)
        probs = jnp.where(causal[None], probs, 0.0)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(-1, H * dv)

    n_blocks = -(-T // QUERY_BLOCK)
    rows = jnp.arange(n_blocks * QUERY_BLOCK).reshape(n_blocks, QUERY_BLOCK)
    out = jax.lax.map(block, rows).reshape(n_blocks * QUERY_BLOCK, H * dv)[:T]
    return out @ _f32(layer["wo"])


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))) @ _f32(w2)


def _experts(layer, cfg, h):
    """-> (held experts' part + shared expert [T, D], router margin [T])."""
    T = h.shape[0]
    E = cfg.n_routed_experts
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]))
    biased = scores + layer["router_bias"]
    order = jnp.argsort(-biased, axis=-1)                       # full sort
    chosen = order[:, :cfg.moe_top_k]
    ranked = jnp.take_along_axis(biased, order, axis=-1)
    margin = ranked[:, cfg.moe_top_k - 1] - ranked[:, cfg.moe_top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    gate = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(weights)
    lo, hi = cfg.experts_held

    def one_expert(out, held):                       # the held ones, in turn
        w1, w3, w2, column = held
        return out + column[:, None] * _swiglu(h, w1, w3, w2), None

    out, _ = jax.lax.scan(
        one_expert, _swiglu(h, layer["shared_w1"], layer["shared_w3"],
                            layer["shared_w2"]),
        (layer["w1"], layer["w3"], layer["w2"], gate[:, lo:hi].T))
    return out, margin


@functools.partial(jax.jit, static_argnames=("cfg",))
def layer_step(layer, x, pos, cfg):
    """One decoder layer over the whole sequence x [T, D]: -> (x, router
    margin [T])."""
    x = x + _attention(layer, cfg, _rms(x, layer["attn_norm"], cfg.norm_eps), pos)
    h = _rms(x, layer["ffn_norm"], cfg.norm_eps)
    if "router" in layer:
        y, margin = _experts(layer, cfg, h)
    else:
        y = _swiglu(h, layer["w1"], layer["w3"], layer["w2"])
        margin = jnp.full((x.shape[0],), jnp.inf)
    return x + y, margin


def trace(params, model_config, tokens, positions=None) -> dict:
    """The whole sequence of T tokens: ``logits`` [T, V] of the main model,
    ``draft_logits`` [T - 1, V] of the block (row i guesses ``t_{i+2}``), both
    of ``positions`` / ``positions - 1`` only where given, ``hidden`` [T, D]
    (before the final norm) and the per-token router margins of each."""
    if model_config.n_group != 1 or model_config.index_topk:
        raise ValueError("this reference routes over one group and selects "
                         "nothing: not this configuration's model")
    cfg, block = model_config, params["mtp"]
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        pos = jnp.arange(len(tokens))
        embedded = _f32(params["embed"])[tokens]
        x = embedded
        margin = jnp.full((len(tokens),), jnp.inf)
        for layer in params["layers"]:
            x, layer_margin = layer_step(layer, x, pos, cfg)
            margin = jnp.minimum(margin, layer_margin)
        hidden = x
        # the block over positions 0..T-2, each with the token that follows it
        joined = jnp.concatenate(
            [_rms(embedded[1:], _f32(block["enorm"]), cfg.norm_eps),
             _rms(hidden[:-1], _f32(block["hnorm"]), cfg.norm_eps)], axis=-1)
        y, draft_margin = layer_step(block["layer"], joined @ _f32(block["eh_proj"]),
                                     pos[:-1], cfg)
        if positions is not None:
            at = jnp.asarray(positions)
            x, y, margin, draft_margin = x[at], y[at - 1], margin[at], draft_margin[at - 1]
        head = _f32(params["lm_head"])
        logits = _rms(x, _f32(params["final_norm"]), cfg.norm_eps) @ head
        draft_logits = _rms(y, _f32(block["norm"]), cfg.norm_eps) @ head
    return {"logits": logits, "draft_logits": draft_logits, "hidden": hidden,
            "router_margin": margin, "draft_router_margin": draft_margin}


def forward(params, model_config, tokens, positions):
    """[len(positions), 2 V]: at the row of position ``p`` (``p`` >= 1) the
    main logits at ``p`` beside the block's at ``p - 1``, and per position the
    smaller of the two router margins."""
    out = trace(params, model_config, tokens, positions)
    margins = jnp.minimum(out["router_margin"], out["draft_router_margin"])
    return (jnp.concatenate([out["logits"], out["draft_logits"]], axis=-1),
            jnp.where(jnp.isfinite(margins), margins, 0.0))
