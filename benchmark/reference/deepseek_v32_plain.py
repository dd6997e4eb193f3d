"""Plain reference of the latent-attention / sparse-selector / shared + routed
expert decoder (DeepSeek-V3.2's layer), for the comparison that decides
``correct``. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernel, no batching,
nothing imported from ``mcp_context_forge_tpu``; the engine's own weight tree,
each weight cast to float32 where it is used.

Deliberately NOT the program's formulation:

- attention in the NON-absorbed form: per head, ``k_nope`` and ``v`` are
  expanded from the latent ``c`` through ``W_kvb`` and scores are
  ``q_nope . k_nope + q_rope . k_rope``;
- the index scores as a full ``[T, S]`` matrix, and the selected set by a full
  sort of every row (the ``k`` first of the descending order);
- experts one after the other over the ones held, each over every token,
  weighted by a dense ``[T, E]`` gate matrix; the router in float32 like
  everything else.

A layer is ONE jitted program (two kinds of layer x the check's lengths: a
handful of compiles; op by op it was some 700 of about a second each on the
chip, and its float32 temporaries filled the HBM beside the engine), queries
in blocks of ``QUERY_BLOCK`` so that a check prompt of 4608 tokens fits there.
``margins``: per requested position the smallest, over the layers, of the gap
between the 8th and 9th corrected router score and of the gap between the
``k``-th and ``(k+1)``-th index score (relative to the row's spread): the two
places where rounding can flip a choice.
"""

from __future__ import annotations

import functools
import math

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
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.max_seq_len <= cfg.rope_original_max:
        return freqs.astype(np.float32)
    find = lambda rot: (dim * math.log(cfg.rope_original_max / (rot * 2 * math.pi))
                        / (2 * math.log(base)))
    low = max(math.floor(find(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(find(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (freqs / cfg.rope_factor * ramp + freqs * (1.0 - ramp)).astype(np.float32)


def _scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.max_seq_len > cfg.rope_original_max:
        scale *= (0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0) ** 2
    return scale


def _rope(x, pos, inv_freq):
    """x [T, ..., d]: halves pair up (x[i] with x[i + d/2])."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _attention(layer, cfg, h, pos):
    """-> (attention output [T, D], selected [T, T] bool, index margin [T])."""
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

    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    q_i = (c_q @ _f32(layer["idx_wq_b"])).reshape(T, Hi, Di)
    q_i = jnp.concatenate([_rope(q_i[..., :dr], pos, inv_freq), q_i[..., dr:]], -1)
    k_i = h @ _f32(layer["idx_wk"])
    mean = jnp.mean(k_i, axis=-1, keepdims=True)
    k_i = ((k_i - mean) * jax.lax.rsqrt(jnp.mean((k_i - mean) ** 2, -1, keepdims=True)
                                        + cfg.norm_eps)
           * layer["idx_k_norm"] + layer["idx_k_bias"])
    k_i = jnp.concatenate([_rope(k_i[:, :dr], pos, inv_freq), k_i[:, dr:]], -1)
    w_i = (h @ _f32(layer["idx_w"])) * Hi ** -0.5

    k, scale = cfg.index_topk, _scale(cfg)

    def block(rows):
        """One block of queries (row numbers; past T: padding, masked out)."""
        live = rows < T
        at = jnp.minimum(rows, T - 1)
        causal = (pos[None, :] <= pos[at][:, None]) & live[:, None]      # [b, S]
        dots = jnp.einsum("thd,sd->ths", q_i[at], k_i)
        index = Di ** -0.5 * jnp.einsum("ths,th->ts", jax.nn.relu(dots), w_i[at])
        index = jnp.where(causal, index, NEG)
        order = jnp.argsort(-index, axis=-1)                        # full sort
        rank = jnp.argsort(order, axis=-1)
        selected = (rank < k) & causal
        if T > k:
            ranked = jnp.take_along_axis(index, order, axis=-1)
            spread = jnp.std(jnp.where(causal, index, 0.0), axis=-1) + 1e-9
            gap = jnp.where(jnp.sum(causal, axis=-1) > k,
                            (ranked[:, k - 1] - ranked[:, k]) / spread, jnp.inf)
        else:
            gap = jnp.full((rows.shape[0],), jnp.inf)
        scores = (jnp.einsum("thd,shd->hts", q_n[at], k_n)
                  + jnp.einsum("thd,sd->hts", q_r[at], k_r)) * scale
        probs = jax.nn.softmax(jnp.where(selected[None], scores, NEG), axis=-1)
        probs = jnp.where(selected[None], probs, 0.0)
        out = jnp.einsum("hts,shd->thd", probs, v).reshape(-1, H * dv)
        return out, selected, gap

    n_blocks = -(-T // QUERY_BLOCK)
    rows = jnp.arange(n_blocks * QUERY_BLOCK).reshape(n_blocks, QUERY_BLOCK)
    out, selected, gap = jax.lax.map(block, rows)
    flat = lambda a: a.reshape(n_blocks * QUERY_BLOCK, *a.shape[2:])[:T]
    return flat(out) @ _f32(layer["wo"]), flat(selected), flat(gap)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))) @ _f32(w2)


def _experts(layer, cfg, h):
    """-> (held experts' part + shared expert [T, D], router margin [T])."""
    T = h.shape[0]
    E, G = cfg.n_routed_experts, cfg.n_group
    scores = jax.nn.sigmoid(h @ _f32(layer["router"]))
    biased = scores + layer["router_bias"]
    groups = biased.reshape(T, G, E // G)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
    keep_groups = jnp.argsort(-group_score, axis=-1)[:, :cfg.topk_group]
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], keep_groups].set(True)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), biased, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1)
    chosen = order[:, :cfg.moe_top_k]
    ranked = jnp.take_along_axis(masked, order, axis=-1)
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
    """One decoder layer over the whole sequence x [T, D]: -> (x, selected
    [T, T], index margin [T], router margin [T])."""
    attn, selected, index_margin = _attention(
        layer, cfg, _rms(x, layer["attn_norm"], cfg.norm_eps), pos)
    x = x + attn
    h = _rms(x, layer["ffn_norm"], cfg.norm_eps)
    if "router" in layer:
        y, router_margin = _experts(layer, cfg, h)
    else:
        y = _swiglu(h, layer["w1"], layer["w3"], layer["w2"])
        router_margin = jnp.full((x.shape[0],), jnp.inf)
    return x + y, selected, index_margin, router_margin


def trace(params, model_config, tokens, positions=None, keep_selected=True) -> dict:
    """The whole sequence: logits [T, V] (of ``positions`` only where given),
    per-layer selected sets [T, T] and the per-token margins."""
    cfg = model_config
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(len(tokens))
        x = _f32(params["embed"][jnp.asarray(tokens)])
        router_margin = jnp.full((len(tokens),), jnp.inf)
        index_margin = jnp.full((len(tokens),), jnp.inf)
        selected = []
        for layer in params["layers"]:
            x, sel, gap, margin = layer_step(layer, x, pos, cfg)
            if keep_selected:
                selected.append(sel)
            index_margin = jnp.minimum(index_margin, gap)
            router_margin = jnp.minimum(router_margin, margin)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _rms(x, _f32(params["final_norm"]), cfg.norm_eps)
        logits = x @ _f32(params["lm_head"])
    return {"logits": logits, "selected": selected,
            "router_margin": router_margin, "index_margin": index_margin}


def forward(params, model_config, tokens, positions):
    """Logits [len(positions), V] of the positions asked for, and per position
    the smaller of its router margin and its (relative) index margin."""
    out = trace(params, model_config, tokens, positions, keep_selected=False)
    margins = jnp.minimum(out["router_margin"],
                          out["index_margin"])[jnp.asarray(positions)]
    return out["logits"], jnp.where(jnp.isfinite(margins), margins, 0.0)
