"""Latent-attention decoder with shared + routed experts, functional like
``models/llama.py``, with two parts that a model configuration has or lacks:
a learned sparse selector (the DeepSeek-V3.2 layer: ``index_topk`` > 0) and a
multi-token-prediction block that drafts for the engine's verify step
(``n_mtp_blocks`` 1). One module, the latent path written once.

Per layer, pre-norm residual: ``x += MLA(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``.

- **Latent attention (MLA).** ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` is
  ``(q_nope, q_rope)`` a head; ``(c_raw, k_raw) = x W_kva``, ``c =
  RMSNorm(c_raw)``, ``k_rope = RoPE(k_raw)`` ONE for all heads. The cache holds
  ``c || k_rope`` a token a layer, stored padded with zeros to whole lanes
  (``kv/paged_cache.py: LatentKVState``, ``stored_width``).
  Every step runs the ABSORBED form: ``q~ = q_nope W_uk^T`` so a score is one
  dot product with the cached vector, and ``W_uv`` applies after the weighted
  sum of latents. ``scale = (d_nope + d_rope)^-0.5 * mscale^2`` (YaRN).
- **Selector** (where the model has one; without it a query attends to every
  position it may see, visibility is the only bias, and the cache holds no
  second pool). ``q^I = c_q W^I_qb`` (heads x dims), ``k^I = LayerNorm(x
  W^I_k)``, RoPE on the leading rotary dims of both, ``w = x W^I_w * Hi^-0.5``;
  ``I[t, s] = d^-0.5 * sum_j w[t, j] relu(q^I_j[t] . k^I[s])``; a query attends
  to the ``min(index_topk, t + 1)`` positions of largest ``I[t, .]`` —
  exactly, by a threshold search (``ops/mla_attention.py``). ``k^I`` is the
  cache's second pool.
- **FFN.** The first ``n_dense_layers`` layers: SwiGLU. The rest: sigmoid
  router in float32 with a correction bias, group-limited top-k over ALL
  routed experts; this engine computes the pairs that land on
  ``experts_held`` (the dropless grouped kernel for steps of at least
  ``moe_block`` tokens, the expert scan for narrower ones — decode) plus the
  shared expert. What absent experts would add is left out, as their chips
  of an expert-parallel deployment would add it.
- **Rotary layout** (both places): the two halves of the rotary dims pair up
  (``x[i]`` with ``x[i + d/2]``), as ``models/llama.apply_rope``. With random
  weights the layout is a permutation of weight columns.

- **Multi-token-prediction block** (``params["mtp"]``; DeepSeek-V3 report
  section 2.2). With ``h_i`` the last layer's output at position ``i`` BEFORE
  ``final_norm`` and ``t_{i+1}`` the token that follows: ``x_i =
  [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh``, ``y_i = Layer(x_i)`` (one
  whole decoder layer of the expert kind: latent attention over the block's
  OWN entries 0..i at rotary position ``i``, cache layer ``n_layers``),
  ``draft_i = Head(RMSNorm_s(y_i))`` predicts ``t_{i+2}``; embedding and head
  are the model's. :func:`draft_step` runs it over a ``[B, S]`` block beside
  the main pass (prefill, chunk rounds, every verify step), so its cache is
  always built. The engine asks :func:`drafts_on_device` and then drives a
  decode dispatch as a verify step of ``1 + n_mtp_blocks`` positions a row
  that also drafts (``engine._decode_and_sample_draft``). A rejected draft's
  latent entries (main layers and the block's) are dead by position and
  overwritten by the next step.

Not done: FP8 and the Hadamard rotation of the published selector
(orthogonal, cancels in the product); a verify-width path through the
selector.

Every step function also returns a small float32 vector of counts
(``STEP_AUX``): tokens through expert layers, token-expert pairs on held
experts, and the rows' summed selected / context share with the row count
(:func:`draft_step` adds the block's tokens and pairs); the engine reads it
back with the sampled tokens.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .configs import DeepseekConfig
from .llama import _dense, _ffn, lm_logits, rms_norm
from ..kv.paged_cache import (LatentKVState, gather_pool, init_kv_state,  # noqa: F401 (family names)
                              kv_logical, kv_page_bytes, lane_padded,
                              write_latent_kv)
from ..ops import mla_attention as mla
from ..ops.attention import on_tpu
from ..quantize import embed_rows, qmm

STEP_AUX = True
STEP_KIND = "token"  # a decode step yields one token a row (models/__init__.py)
NEG_INF = mla.NEG_INF
# query positions a row up to which a step is a verify step (each position's
# heads a group of the latent kernel's rows); wider steps are chunks of queries
_VERIFY_POSITIONS = 8


# ----------------------------------------------------------------- rotary

def yarn_inv_freq(config: DeepseekConfig) -> np.ndarray:
    """YaRN frequencies of the rotary dims, as published: interpolated by
    ``rope_factor`` below the correction range, untouched above it."""
    dim, base = config.qk_rope_head_dim, config.rope_theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if config.max_seq_len <= config.rope_original_max:
        return freqs.astype(np.float32)

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(config.rope_original_max
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(config.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(config.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (freqs / config.rope_factor * (1 - smooth)
            + freqs * smooth).astype(np.float32)


def softmax_scale(config: DeepseekConfig) -> float:
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    if config.max_seq_len > config.rope_original_max:
        mscale = (0.1 * config.rope_mscale_all_dim
                  * math.log(config.rope_factor) + 1.0)
        scale *= mscale * mscale
    return scale


def _rope(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray) -> jax.Array:
    """x: [B, S, ..., d] rotary dims (d = 2 * len(inv_freq)); positions [B, S]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, S, d/2]
    angles = angles.reshape(*positions.shape, *([1] * (x.ndim - 3)), -1)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
                eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * weight + bias).astype(x.dtype)


# ----------------------------------------------------------------- params

def layer_kind(config: DeepseekConfig, layer: int) -> str:
    return config.ffn_kind(layer)


def init_layer(config: DeepseekConfig, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "experts") -> dict[str, Any]:
    """One layer's random weights; ``kind`` is its FFN (dense | experts)."""
    c = config
    D, H = c.dim, c.n_heads
    k = jax.random.split(key, 16)
    ones = lambda n: jnp.ones((n,), dtype=jnp.float32)
    layer = {
        "attn_norm": ones(D),
        "wq_a": _dense(k[0], (D, c.q_lora_rank), D, dtype),
        "q_norm": ones(c.q_lora_rank),
        "wq_b": _dense(k[1], (c.q_lora_rank,
                              H * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                       c.q_lora_rank, dtype),
        "wkv_a": _dense(k[2], (D, c.latent_dim), D, dtype),
        "kv_norm": ones(c.kv_lora_rank),
        "wkv_b": _dense(k[3], (c.kv_lora_rank,
                               H * (c.qk_nope_head_dim + c.v_head_dim)),
                        c.kv_lora_rank, dtype),
        "wo": _dense(k[4], (H * c.v_head_dim, D), H * c.v_head_dim, dtype),
        "ffn_norm": ones(D),
    }
    if c.has_selector:
        layer.update({
            "idx_wq_b": _dense(k[5], (c.q_lora_rank,
                                      c.index_n_heads * c.index_head_dim),
                               c.q_lora_rank, dtype),
            "idx_wk": _dense(k[6], (D, c.index_head_dim), D, dtype),
            "idx_k_norm": ones(c.index_head_dim),
            "idx_k_bias": jnp.zeros((c.index_head_dim,), dtype=jnp.float32),
            "idx_w": _dense(k[7], (D, c.index_n_heads), D, dtype),
        })
    if kind == "dense":
        F = c.ffn_hidden
        layer.update({"w1": _dense(k[8], (D, F), D, dtype),
                      "w3": _dense(k[9], (D, F), D, dtype),
                      "w2": _dense(k[10], (F, D), F, dtype)})
        return layer
    F, E, S = c.moe_ffn_hidden, c.n_held, c.n_shared_experts * c.moe_ffn_hidden
    layer.update({
        # the gate is computed in float32 (as the published modelling code
        # does): 256 scores a token, and it keeps a choice from flipping on
        # bf16 rounding
        "router": _dense(k[8], (D, c.n_routed_experts), D, jnp.float32),
        "router_bias": 0.1 * jax.random.normal(
            k[9], (c.n_routed_experts,), dtype=jnp.float32),
        "w1": _dense(k[10], (E, D, F), D, dtype),
        "w3": _dense(k[11], (E, D, F), D, dtype),
        "w2": _dense(k[12], (E, F, D), F, dtype),
        "shared_w1": _dense(k[13], (D, S), D, dtype),
        "shared_w3": _dense(k[14], (D, S), D, dtype),
        "shared_w2": _dense(k[15], (S, D), S, dtype),
    })
    return layer


def init_trunk(config: DeepseekConfig, embed_key: jax.Array,
               head_key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    """Everything outside the layers: embedding, final norm, head and, where
    the model has one, the multi-token-prediction block (its keys folded from
    the head's)."""
    D = config.dim
    trunk = {
        "embed": _dense(embed_key, (config.vocab_size, D), D, dtype),
        "final_norm": jnp.ones((D,), dtype=jnp.float32),
        "lm_head": _dense(head_key, (D, config.vocab_size), D, dtype),
    }
    if config.n_mtp_blocks:
        ones = jnp.ones((D,), dtype=jnp.float32)
        trunk["mtp"] = {
            "enorm": ones, "hnorm": ones, "norm": ones,
            "eh_proj": _dense(jax.random.fold_in(head_key, 1), (2 * D, D),
                              2 * D, dtype),
            "layer": init_layer(config, jax.random.fold_in(head_key, 2),
                                dtype, kind="experts"),
        }
    return trunk


def init_keys(config: DeepseekConfig, key: jax.Array) -> jax.Array:
    """[n_layers + 2] keys: one per layer, then the embedding's and the head's."""
    return jax.random.split(key, config.n_layers + 2)


def init_params(config: DeepseekConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype,
                                   kind=config.ffn_kind(i))
                        for i in range(config.n_layers)]
    return params


def params_logical(config: DeepseekConfig) -> dict[str, Any]:
    """Logical sharding names matching init_params' tree. Everything
    replicates: the family runs on a ``model`` axis of one device only
    (:func:`refusals`)."""
    attn = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
            "wkv_b", "wo", "ffn_norm")
    if config.has_selector:
        attn += ("idx_wq_b", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w")
    ffn = {"dense": ("w1", "w3", "w2"),
           "experts": ("router", "router_bias", "w1", "w3", "w2",
                       "shared_w1", "shared_w3", "shared_w2")}
    layer = lambda kind: {name: "replicated" for name in attn + ffn[kind]}
    logical = {
        "embed": "replicated", "final_norm": "replicated",
        "lm_head": "replicated",
        "layers": [layer(config.ffn_kind(i)) for i in range(config.n_layers)],
    }
    if config.n_mtp_blocks:
        logical["mtp"] = {"enorm": "replicated", "hnorm": "replicated",
                          "norm": "replicated", "eh_proj": "replicated",
                          "layer": layer("experts")}
    return logical


def param_count(config: DeepseekConfig) -> int:
    """Parameters HELD here: the routed experts count ``n_held`` times."""
    c = config
    D, H = c.dim, c.n_heads
    attn = (D * c.q_lora_rank + c.q_lora_rank
            + c.q_lora_rank * H * (c.qk_nope_head_dim + c.qk_rope_head_dim)
            + D * c.latent_dim + c.kv_lora_rank
            + c.kv_lora_rank * H * (c.qk_nope_head_dim + c.v_head_dim)
            + H * c.v_head_dim * D + 2 * D)
    if c.has_selector:
        attn += (c.q_lora_rank * c.index_n_heads * c.index_head_dim
                 + D * c.index_head_dim + 2 * c.index_head_dim
                 + D * c.index_n_heads)
    dense = 3 * D * c.ffn_hidden
    experts = (D * c.n_routed_experts + c.n_routed_experts
               + (c.n_held + c.n_shared_experts) * 3 * D * c.moe_ffn_hidden)
    n_dense = min(c.n_dense_layers, c.n_layers)
    block = c.n_mtp_blocks * (3 * D + 2 * D * D + attn + experts)
    return (2 * c.vocab_size * D + D + c.n_layers * attn
            + n_dense * dense + (c.n_layers - n_dense) * experts + block)


# ------------------------------------------------ what the engine looks up

def prefill_impl(impl: str, mesh, seq: int, config: DeepseekConfig,
                 itemsize: int = 2) -> str:
    """The family has ONE attention path (over the cache it just wrote), so a
    dense prefill's choice is the paged one's."""
    return "pallas" if on_tpu(mesh) else "gather"


def prefill_unit(mesh, config: DeepseekConfig) -> int:
    """Tokens a dense prefill's length must be a whole number of: on a TPU
    the query tiles of the selector's and the latent attention's kernels."""
    if not on_tpu(mesh):
        return 1
    return math.lcm(mla._INDEX_QUERY_TILE, mla._ATTN_QUERY_TILE)


def drafts_on_device(config: DeepseekConfig) -> bool:
    """Whether the model drafts for the verify step itself
    (``models/__init__.py``): it does where it has the block."""
    return config.n_mtp_blocks > 0


def paged_impl(mesh, config: DeepseekConfig, kv: LatentKVState) -> str:
    return "pallas" if on_tpu(mesh) else "gather"


def refusals(config: DeepseekConfig, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings this family cannot serve yet, each with its reason.
    The engine refuses to build on any of them; nothing falls back."""
    why = []
    if mesh.shape.get("model", 1) > 1:
        why.append("a mesh with more than one device on the model axis: the "
                   "latent pools, the absorbed attention and the held experts "
                   "have no sharding over it yet (tensor/expert parallelism "
                   "over a mesh)")
    if engine_config.spec_decode:
        if config.has_selector:
            why.append("spec_decode: the selector has no verify-width path "
                       "(its index kernel scores one position or whole query "
                       "tiles a row)")
        if not config.n_mtp_blocks:
            why.append("spec_decode: the model has no multi-token-prediction "
                       "block to draft with, and prompt-lookup drafts are "
                       "the GQA trunk's")
        elif engine_config.spec_k != 1 + config.n_mtp_blocks:
            why.append(f"spec_k={engine_config.spec_k}: a verify step is the "
                       f"last token and one draft a block, "
                       f"{1 + config.n_mtp_blocks} positions a row")
    if engine_config.sp_impl != "none":
        why.append(f"sp_impl={engine_config.sp_impl!r}: no sequence-parallel "
                   "prefill for latent attention")
    if engine_config.kv_quant:
        why.append(f"kv_quant={engine_config.kv_quant!r}: the latent pools "
                   "are full precision only")
    if engine_config.quant:
        why.append(f"quant={engine_config.quant!r}: the latent and selector "
                   "projections and the float32 router have no int8 path")
    if tiers:
        why.append("KV tiers / fabric (prefix_tiers, a pool's prefix index "
                   "or tier store): the spill payload carries K and V pages "
                   "of kv heads, not the pools this family declares")
    return why


# ---------------------------------------------------------------- forward

def route(layer: dict[str, Any], config: DeepseekConfig,
          flat: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sigmoid, bias-corrected, group-limited top-k over ALL routed experts:
    flat [T, D] -> (ids [T, k] int32, weights [T, k] float32, corrected
    scores [T, E] float32). Float32 throughout."""
    c = config
    logits = jnp.dot(flat.astype(jnp.float32), layer["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)                              # [T, E]
    biased = scores + layer["router_bias"]
    T, E = biased.shape
    groups = biased.reshape(T, c.n_group, E // c.n_group)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)  # [T, G]
    _, keep = jax.lax.top_k(group_score, c.topk_group)
    kept = jnp.sum(jax.nn.one_hot(keep, c.n_group, dtype=jnp.float32), axis=1)
    masked = jnp.where(kept[:, :, None] > 0, groups, -jnp.inf).reshape(T, E)
    _, ids = jax.lax.top_k(masked, c.moe_top_k)
    weights = jnp.take_along_axis(scores, ids, axis=1)
    weights = (weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True),
                                     1e-20)) * c.routed_scaling_factor
    return ids.astype(jnp.int32), weights, biased


def expert_path(config: DeepseekConfig, mesh, tokens: int,
                dtype=None) -> str:
    """Which formulation the routed experts of a step of ``tokens`` tokens
    trace (the engine counts its steps by the same call): the dropless
    grouped row-blocks for steps of at least ``moe_block`` tokens, the
    expert scan for narrower ones (decode). The activations' ``dtype`` does
    not enter this family's rule."""
    wide = tokens >= config.moe_block
    return ("grouped" if config.moe_impl.startswith("grouped") and wide
            else "scan")


def _expert_ffn(layer: dict[str, Any], config: DeepseekConfig, x: jax.Array,
                valid: jax.Array, mesh=None) -> tuple[jax.Array, jax.Array]:
    """The held routed experts' part + the shared expert. x: [B, S, D];
    valid [B, S] -> ([B, S, D], pairs of valid tokens on held experts)."""
    c = config
    B, S, D = x.shape
    flat = x.reshape(-1, D)
    T = flat.shape[0]
    ids, weights, _ = route(layer, c, flat)
    lo, hi = c.experts_held
    local = ids - lo                       # outside [0, n_held): elsewhere
    here = (ids >= lo) & (ids < hi)
    pairs = jnp.sum((here & valid.reshape(-1, 1)).astype(jnp.float32))
    stacks = {k: layer[k] for k in ("w1", "w3", "w2")}
    if expert_path(c, mesh, T) == "grouped":
        from ..ops.grouped_moe import experts_grouped, plan_sorted_blocks
        plan = plan_sorted_blocks(local, weights, c.n_held, c.moe_block)
        use_pallas = c.moe_impl == "grouped_pallas"
        routed = experts_grouped(
            stacks, flat, plan, impl="pallas" if use_pallas else "xla",
            block=c.moe_block, interpret=use_pallas and not on_tpu(mesh))
    else:
        from ..parallel.moe import expert_scan
        gates = jnp.sum(jax.nn.one_hot(local, c.n_held, dtype=jnp.float32)
                        * weights[:, :, None], axis=1)           # [T, n_held]
        routed = expert_scan(stacks, flat, gates.astype(x.dtype))
    shared = _ffn({"w1": layer["shared_w1"], "w3": layer["shared_w3"],
                   "w2": layer["shared_w2"]}, flat)
    return (routed + shared).reshape(B, S, D), pairs


def _project(layer: dict[str, Any], config: DeepseekConfig, h: jax.Array,
             positions: jax.Array):
    """The attention block's projections of normed hidden states h [B, S, D]
    at rope positions [B, S]: absorbed queries [B, S, H, Dk] (scale folded
    in), the token's cache vectors latent [B, S, Dk] and index key [B, S,
    Di], selector queries [B, S, Hi, Di] and head weights [B, S, Hi]
    (float32, both scales folded in); the selector's three are None for a
    model without one. Dk and Di are the widths the cache STORES
    (``lane_padded`` of ``latent_dim`` and ``index_head_dim``): the same zero
    tail on keys and on queries, so a score is the same sum plus zeros."""
    c = config
    B, S, _ = h.shape
    H, dn, dr, dc = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, \
        c.kv_lora_rank
    inv_freq = yarn_inv_freq(c)
    c_q = rms_norm(qmm(h, layer["wq_a"]), layer["q_norm"], c.norm_eps)
    q = qmm(c_q, layer["wq_b"]).reshape(B, S, H, dn + dr)
    q_rope = _rope(q[..., dn:], positions, inv_freq)
    kv_a = qmm(h, layer["wkv_a"])
    latent = lane_padded(jnp.concatenate(
        [rms_norm(kv_a[..., :dc], layer["kv_norm"], c.norm_eps),
         _rope(kv_a[..., dc:], positions, inv_freq)], axis=-1))
    w_uk = layer["wkv_b"].reshape(dc, H, dn + c.v_head_dim)[..., :dn]
    scale = softmax_scale(c)
    q_abs = jnp.concatenate(
        [jnp.einsum("bshd,chd->bshc", q[..., :dn], w_uk), q_rope], axis=-1)
    q_abs = lane_padded((q_abs.astype(jnp.float32) * scale).astype(h.dtype))
    if not c.has_selector:
        return q_abs, latent, None, None, None

    Hi, Di = c.index_n_heads, c.index_head_dim
    q_idx = qmm(c_q, layer["idx_wq_b"]).reshape(B, S, Hi, Di)
    q_idx = lane_padded(jnp.concatenate(
        [_rope(q_idx[..., :dr], positions, inv_freq), q_idx[..., dr:]], axis=-1))
    k_idx = _layer_norm(qmm(h, layer["idx_wk"]), layer["idx_k_norm"],
                        layer["idx_k_bias"], c.norm_eps)
    k_idx = lane_padded(jnp.concatenate(
        [_rope(k_idx[..., :dr], positions, inv_freq), k_idx[..., dr:]], axis=-1))
    w_idx = (qmm(h, layer["idx_w"]).astype(jnp.float32)
             * (Hi ** -0.5 * Di ** -0.5))
    return q_abs, latent, k_idx, q_idx, w_idx


def _select_bias(scores: jax.Array, k: int, use_pallas: bool) -> jax.Array:
    """scores [B, S, C] (NEG_INF where invisible) -> additive bias [B, S, C]:
    0 on the k largest visible entries of each row, NEG_INF elsewhere."""
    B, S, C = scores.shape
    if k >= C:          # the whole (static) context width fits: keep what is visible
        return jnp.where(scores > 0.5 * NEG_INF, 0.0, NEG_INF).astype(jnp.float32)
    if use_pallas:
        thr = mla.sparse_select_pallas(scores.reshape(B * S, C),
                                       k).reshape(B, S, 1)
    else:
        thr = mla.topk_threshold_reference(scores, k)
    chosen = (scores >= thr) & (scores > 0.5 * NEG_INF)
    return jnp.where(chosen, 0.0, NEG_INF).astype(jnp.float32)


def _visible_bias(attn_pos: jax.Array, width: int) -> jax.Array:
    """A model without a selector: [B, S] last visible positions -> additive
    bias [B, S, width], 0 on cache positions at or before it."""
    cache_pos = jnp.arange(width, dtype=jnp.int32)
    return jnp.where(cache_pos <= attn_pos[..., None], 0.0,
                     NEG_INF).astype(jnp.float32)


def _attention(layer_idx: int, config: DeepseekConfig, q_abs, q_idx, w_idx,
               kv: LatentKVState, tables: jax.Array, attn_pos: jax.Array,
               use_pallas: bool, dense: bool = False,
               visible: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Attention of [B, S] queries over the row's pages (the step's own
    tokens already written): over the selected set, or under ``visible``
    [B, S, C] for a model without a selector. attn_pos [B, S]: the last cache
    position each query sees, -1 for none. -> (latent-space output [B, S, H,
    kv_lora_rank], the bias attended under [B, S, C]). ``dense`` (tests only)
    skips the selection: every visible token is attended."""
    c = config
    B, S, H, _ = q_abs.shape
    if visible is not None:
        bias = visible
    else:
        if use_pallas and S > 1:
            scores = mla.sparse_index_scores_pallas(
                q_idx, w_idx, kv.index_pages, tables, attn_pos,
                layer=layer_idx)
        else:
            scores = mla.index_scores_reference(
                q_idx, w_idx, gather_pool(kv.index_pages, layer_idx, tables),
                attn_pos)
        if dense:
            bias = jnp.where(scores > 0.5 * NEG_INF, 0.0, NEG_INF)
        else:
            bias = _select_bias(scores, c.index_topk, use_pallas)
    if not use_pallas:
        out = mla.mla_attention_reference(
            q_abs.transpose(0, 2, 1, 3), bias,
            gather_pool(kv.latent_pages, layer_idx, tables), c.kv_lora_rank)
        return out.transpose(0, 2, 1, 3), bias
    if S == 1:      # one query's heads are the block's rows, one bias row
        out = mla.mla_paged_attention_pallas(
            q_abs, bias, kv.latent_pages, tables, attn_pos, layer=layer_idx,
            value_dim=c.kv_lora_rank)                    # [B, 1, H, dc]
        return out, bias
    if S <= _VERIFY_POSITIONS:
        # a verify step: each position's heads are a group of rows under the
        # position's own bias row
        out = mla.mla_paged_attention_pallas(
            q_abs, bias, kv.latent_pages, tables,
            jnp.max(attn_pos, axis=1, keepdims=True), layer=layer_idx,
            value_dim=c.kv_lora_rank, group_bias=True)   # [B, S, H, dc]
        return out, bias
    tile = min(S, mla._ATTN_QUERY_TILE)
    max_pos = jnp.max(attn_pos.reshape(B, S // tile, tile), axis=2)
    out = mla.mla_paged_attention_pallas(
        q_abs.transpose(0, 2, 1, 3), bias, kv.latent_pages, tables, max_pos,
        layer=layer_idx, value_dim=c.kv_lora_rank)       # [B, H, S, dc]
    return out.transpose(0, 2, 1, 3), bias


def _layer(cache_idx: int, layer: dict[str, Any], config: DeepseekConfig,
           x: jax.Array, rope_pos: jax.Array, attn_pos: jax.Array,
           live: jax.Array, write_valid: jax.Array, kv: LatentKVState,
           slot_ids: jax.Array, tables: jax.Array, visible: jax.Array | None,
           use_pallas: bool, mesh, dense_attention: bool = False):
    """One decoder layer over x [B, S, D], its cache vectors written to cache
    layer ``cache_idx`` first. ``live`` [B, S]: ``attn_pos >= 0``;
    ``visible``: the bias of a model without a selector (the same in every
    layer). -> (x, kv, the bias it attended
    under, pairs on held experts: None for a dense layer)."""
    c = config
    h = rms_norm(x, layer["attn_norm"], c.norm_eps)
    q_abs, latent, k_idx, q_idx, w_idx = _project(layer, c, h, rope_pos)
    kv = write_latent_kv(kv, cache_idx, latent, k_idx, slot_ids, rope_pos,
                         write_valid)
    out, bias = _attention(cache_idx, c, q_abs, q_idx, w_idx, kv, tables,
                           attn_pos, use_pallas, dense_attention, visible)
    w_uv = layer["wkv_b"].reshape(
        c.kv_lora_rank, c.n_heads,
        c.qk_nope_head_dim + c.v_head_dim)[..., c.qk_nope_head_dim:]
    heads = jnp.einsum("bshc,chd->bshd", out.astype(x.dtype), w_uv)
    x = x + qmm(heads.reshape(*heads.shape[:2], -1), layer["wo"])
    h = rms_norm(x, layer["ffn_norm"], c.norm_eps)
    if "router" in layer:
        y, pairs = _expert_ffn(layer, c, h, live, mesh)
        return x + y, kv, bias, pairs
    return x + _ffn(layer, h), kv, bias, None


def _row_tables(kv: LatentKVState, slot_ids: jax.Array,
                ctx_pages: int | None) -> jax.Array:
    tables = kv.block_tables[slot_ids]
    return tables if ctx_pages is None else tables[:, :ctx_pages]


def _trunk(params: dict[str, Any], config: DeepseekConfig, tokens: jax.Array,
           rope_pos: jax.Array, attn_pos: jax.Array, write_valid: jax.Array,
           kv: LatentKVState, slot_ids: jax.Array, ctx_pages: int | None,
           use_pallas: bool, mesh=None, dense_attention: bool = False
           ) -> tuple[jax.Array, LatentKVState, jax.Array]:
    """Every layer over a [B, S] block of tokens. rope_pos: positions the
    rotary and the cache write use; attn_pos: the last cache position each
    token attends to (-1: a padding or idle row); write_valid: tokens whose
    cache vectors are kept. -> (the last layer's hidden [B, S, D] BEFORE the
    final norm, kv, aux)."""
    c = config
    x = embed_rows(params["embed"], tokens)
    tables = _row_tables(kv, slot_ids, ctx_pages)
    live = attn_pos >= 0
    rows = jnp.sum(live.astype(jnp.float32))
    pairs = jnp.zeros((), jnp.float32)
    visible = None if c.has_selector else _visible_bias(
        attn_pos, tables.shape[1] * kv.page_size)
    bias = visible
    for idx, layer in enumerate(params["layers"]):
        x, kv, bias, layer_pairs = _layer(
            idx, layer, c, x, rope_pos, attn_pos, live, write_valid, kv,
            slot_ids, tables, visible, use_pallas, mesh, dense_attention)
        if layer_pairs is not None:
            pairs = pairs + layer_pairs
    # the rows' selected / context share, from the last layer's selection
    # (1 a live row where nothing selects)
    selected = jnp.sum((bias > 0.5 * NEG_INF).astype(jnp.float32), axis=-1)
    share = jnp.where(live, selected / jnp.maximum(attn_pos + 1, 1), 0.0)
    n_expert_layers = sum("router" in layer for layer in params["layers"])
    aux = jnp.stack([rows * n_expert_layers, pairs, jnp.sum(share), rows])
    return x, kv, aux


def draft_step(params: dict[str, Any], config: DeepseekConfig,
               hidden: jax.Array, next_tokens: jax.Array,
               positions: jax.Array, kv: LatentKVState, slot_ids: jax.Array,
               aux: jax.Array, ctx_pages: int | None = None,
               pick: jax.Array | None = None, paged_impl: str = "gather",
               mesh=None) -> tuple[jax.Array, LatentKVState, jax.Array]:
    """The multi-token-prediction block over a [B, S] block beside the main
    pass that gave ``hidden`` [B, S, D] (its last layer's output BEFORE the
    final norm, ``hidden=True``): ``next_tokens`` [B, S] the token that
    follows each position, ``positions`` absolute (-1 = padding) as the main
    pass had them, ``ctx_pages`` its context width (None: the pages the block
    itself spans, beside a dense prefill). Writes the block's own latent
    entries at those positions and returns (draft logits [B, V] of row
    ``pick`` [B] of each sequence, or [B, S, V] of all, each predicting the
    token after ``next_tokens``; kv; ``aux`` with the block's expert tokens
    and pairs added)."""
    c, block = config, params["mtp"]
    valid = positions >= 0
    if ctx_pages is None:
        ctx_pages = _prefill_pages(hidden.shape[1], kv)
    x = jnp.concatenate(
        [rms_norm(embed_rows(params["embed"], next_tokens), block["enorm"],
                  c.norm_eps),
         rms_norm(hidden, block["hnorm"], c.norm_eps)], axis=-1)
    x = qmm(x, block["eh_proj"])
    tables = _row_tables(kv, slot_ids, ctx_pages)
    visible = None if c.has_selector else _visible_bias(
        positions, tables.shape[1] * kv.page_size)
    y, kv, _, pairs = _layer(
        c.n_layers, block["layer"], c, x, jnp.maximum(positions, 0),
        positions, valid, valid, kv, slot_ids, tables, visible,
        paged_impl == "pallas", mesh)
    if pick is not None:
        y = y[jnp.arange(y.shape[0]), pick]
    rows = jnp.sum(valid.astype(jnp.float32))
    aux = aux.at[:2].add(jnp.stack([rows, pairs]))
    return (lm_logits(params, rms_norm(y, block["norm"], c.norm_eps)), kv,
            aux)


def prefill_with_history(params: dict[str, Any], config: DeepseekConfig,
                         tokens: jax.Array, positions: jax.Array,
                         kv: LatentKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None,
                         dense_attention: bool = False, hidden: bool = False
                         ) -> tuple[jax.Array, LatentKVState, jax.Array]:
    """A [B, S] block of prompt tokens at ABSOLUTE positions (-1 = padding)
    over whatever the rows' pages already hold: dense prefill (history 0),
    prefix-cache suffixes, chunk rounds and verify steps alike. Arguments as
    ``models.llama.prefill_with_history``. -> (logits, kv, aux), and with
    ``hidden`` the last layer's output [B, S, D] before the final norm beside
    them (what :func:`draft_step` takes)."""
    valid = positions >= 0
    h, kv, aux = _trunk(params, config, tokens, jnp.maximum(positions, 0),
                        positions, valid, kv, slot_ids, ctx_pages,
                        paged_impl == "pallas", mesh, dense_attention)
    x = rms_norm(h, params["final_norm"], config.norm_eps)
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]
    out = (lm_logits(params, x), kv, aux)
    return (*out, h) if hidden else out


def prefill(params: dict[str, Any], config: DeepseekConfig, tokens: jax.Array,
            positions: jax.Array, kv: LatentKVState, slot_ids: jax.Array,
            attn_impl: str = "gather", mesh=None,
            last_idx: jax.Array | None = None, hidden: bool = False
            ) -> tuple[jax.Array, LatentKVState, jax.Array]:
    """A prompt inside one bucket: the history path with no history, reading
    back the pages it just wrote (the cache IS the attention's operand in the
    absorbed form), over as many pages as the bucket spans."""
    return prefill_with_history(
        params, config, tokens, positions, kv, slot_ids,
        ctx_pages=_prefill_pages(tokens.shape[1], kv), last_idx=last_idx,
        paged_impl=attn_impl, mesh=mesh, hidden=hidden)


def _prefill_pages(length: int, kv: LatentKVState) -> int:
    """Pages a dense prefill of ``length`` positions attends over."""
    return min(-(-length // kv.page_size), kv.block_tables.shape[1])


def decode_step(params: dict[str, Any], config: DeepseekConfig,
                tokens: jax.Array, positions: jax.Array, kv: LatentKVState,
                slot_ids: jax.Array, seq_lens: jax.Array,
                ctx_pages: int | None = None,
                write_mask: jax.Array | None = None,
                paged_impl: str = "gather", mesh=None
                ) -> tuple[jax.Array, LatentKVState, jax.Array]:
    """One token a slot over the latent cache; arguments as
    ``models.llama.decode_step``. -> (logits [B, V], kv, aux)."""
    valid = (jnp.ones_like(seq_lens, dtype=bool) if write_mask is None
             else write_mask)
    x, kv, aux = _trunk(params, config, tokens[:, None], positions[:, None],
                        (seq_lens - 1)[:, None], valid[:, None], kv, slot_ids,
                        ctx_pages, paged_impl == "pallas", mesh)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return lm_logits(params, x[:, 0]), kv, aux
