"""Block-diffusion decoder (the SDAR layer stack and its generation rule),
functional like ``models/llama.py``, whose parts it calls.

**The layers** are the GQA trunk's with two differences. Attention:
``q = RMSNorm_hd(x W_q)``, ``k = RMSNorm_hd(x W_k)`` per head over
``head_dim`` with one weight vector all heads share, BEFORE the rotation
(QK-norm, which the trunk lacks); RoPE at the TRUE positions; then softmax
attention under a BLOCK-CAUSAL mask: key j is visible to query i iff ``p_j //
Bl <= p_i // Bl`` (``Bl = config.block_length``). Every mask in the trunk's
kernels and references compares absolute positions (``k_pos <= q_pos``), so
the mask is the causal one with the query's MASK position rounded up to its
block's last position (``ops/attention.py: block_last``): the flash kernel and
its reference take ``mask_block``, the paged chunk kernel and the gather
reference are handed the rounded positions in place of the true ones. ``Bl``
divides the page size and every kernel tile, so no block straddles a page or
a tile. FFN: every layer routes over ``n_experts`` small experts (softmax
over all, the top ``moe_top_k`` renormalised), through the trunk's
``_ffn_block`` and its two formulations (``expert_path``: with 128 x top-8 a
block step of 32 rows takes the row-block kernel at 16 rows, like a prefill
at ``moe_block``; idle rows, position -1, get no row).

**What the logits mean.** ``logits_i`` are the distribution of token i ITSELF
(no shift): a position holding ``mask_token_id`` is predicted from its own
row. A prompt therefore yields no token; generation fills blocks.

**Prefill** (``prefill``, ``prefill_with_history``): the first ``Bl * (P //
Bl)`` prompt tokens under the block-causal mask, K/V stored, nothing sampled
(``head=False`` skips the head). The ``P mod Bl`` tokens left open the first
generation block as known positions.

**The block step** (``block_step``, one device dispatch a block a row): rows
``[B, Bl]`` of tokens, true positions and MASKED flags (a flag of the position,
not a comparison with the id: a sampled token may equal ``mask_token_id``). A
denoise pass runs the ``Bl`` positions through all layers (writing their K/V
at the block's page, attending to the stored K/V of earlier blocks and
bidirectionally to itself), samples ``x0`` at every masked position with its
confidence (``sampling.sample_with_confidence``) and fills the positions
``sampling.fill_positions`` picks. Passes repeat inside a ``lax.while_loop``
until no live row has a masked position (at most ``denoising_steps``); then
one COMMIT pass over the final tokens (no head) writes the K/V later blocks
attend to. Rows advance in lockstep; a row that finished early rides along
unchanged. Departure from the published generation loop, which leaves ties
between equal confidences to ``torch.topk``: ties go to the lower position.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from . import llama
from .configs import SdarConfig
from .llama import (_dense, _ffn_block, _history_attention, _history_tile,  # noqa: F401 (expert_path: a family name, the trunk's rule)
                    apply_rope, expert_path, lm_logits, rms_norm)
from ..kv.paged_cache import (PagedKVState, gather_kv, init_kv_state,  # noqa: F401 (family names)
                              kv_logical, kv_page_bytes, write_prefill_kv)
from ..ops.attention import (block_last, causal_attention,
                             select_paged_attention, select_prefill_attention)
from ..quantize import embed_rows, qmm
from ..sampling import (SamplingParams, fill_counts, fill_positions,
                        sample_with_confidence)

STEP_AUX = False
STEP_KIND = "block"  # a decode dispatch fills and commits a block a row


# ----------------------------------------------------------------- params

def layer_kind(config: SdarConfig, layer: int) -> str:
    """Every layer has the same tree."""
    return "block"


def init_layer(config: SdarConfig, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "block") -> dict[str, Any]:
    c = config
    D, F, E = c.dim, c.ffn_hidden, c.n_experts
    Q, KV = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    k = jax.random.split(key, 8)
    ones = lambda n: jnp.ones((n,), dtype=jnp.float32)
    return {
        "attn_norm": ones(D), "ffn_norm": ones(D),
        "wq": _dense(k[0], (D, Q), D, dtype),
        "wk": _dense(k[1], (D, KV), D, dtype),
        "wv": _dense(k[2], (D, KV), D, dtype),
        "wo": _dense(k[3], (Q, D), Q, dtype),
        "q_norm": ones(c.head_dim), "k_norm": ones(c.head_dim),
        "router": _dense(k[4], (D, E), D, dtype),
        "w1": _dense(k[5], (E, D, F), D, dtype),
        "w3": _dense(k[6], (E, D, F), D, dtype),
        "w2": _dense(k[7], (E, F, D), F, dtype),
    }


def init_trunk(config: SdarConfig, embed_key: jax.Array, head_key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    return {
        "embed": _dense(embed_key, (config.vocab_size, config.dim),
                        config.dim, dtype),
        "final_norm": jnp.ones((config.dim,), dtype=jnp.float32),
        "lm_head": _dense(head_key, (config.dim, config.vocab_size),
                          config.dim, dtype),
    }


def init_keys(config: SdarConfig, key: jax.Array) -> jax.Array:
    """[n_layers + 2] keys: one per layer, then the embedding's and the head's."""
    return jax.random.split(key, config.n_layers + 2)


def init_params(config: SdarConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype)
                        for i in range(config.n_layers)]
    return params


def params_logical(config: SdarConfig) -> dict[str, Any]:
    """The trunk's logical names (so ``quantize_tree`` takes the projections
    and the expert stacks); the norms and the router stay full precision."""
    layer = {"attn_norm": "replicated", "ffn_norm": "replicated",
             "wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
             "wo": "attn_out", "q_norm": "replicated", "k_norm": "replicated",
             "router": "replicated", "w1": "moe_up", "w3": "moe_up",
             "w2": "moe_down"}
    return {"embed": "vocab_in", "final_norm": "replicated",
            "lm_head": "vocab_out",
            "layers": [dict(layer) for _ in range(config.n_layers)]}


def param_count(config: SdarConfig) -> int:
    c = config
    attention = (c.dim * (c.n_heads + 2 * c.n_kv_heads) * c.head_dim
                 + c.n_heads * c.head_dim * c.dim + 2 * c.head_dim)
    experts = c.n_experts * 3 * c.dim * c.ffn_hidden + c.dim * c.n_experts
    return (2 * c.vocab_size * c.dim + c.dim
            + c.n_layers * (attention + experts + 2 * c.dim))


# ------------------------------------------------ what the engine looks up

def prefill_impl(impl: str, mesh, seq: int, config: SdarConfig,
                 itemsize: int = 2) -> str:
    return select_prefill_attention(impl, mesh, seq, config.head_dim,
                                    config.n_kv_heads, itemsize)


def prefill_unit(mesh, config: SdarConfig) -> int:
    """Whole blocks (a prefill ends on one, and the block-causal mask cuts
    none) that are whole units of the trunk's, whose attention it runs."""
    return math.lcm(config.block_length, llama.prefill_unit(mesh, config))


def paged_impl(mesh, config: SdarConfig, kv: PagedKVState) -> str:
    return select_paged_attention(mesh, config.head_dim, kv.page_size,
                                  config.n_kv_heads, kv.quantized)


def refusals(config: SdarConfig, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings this family cannot serve, each with its reason. The
    engine refuses to build on any of them; nothing falls back."""
    Bl = config.block_length
    why = []
    if any(k > 1 for k in engine_config.k_rungs()):
        why.append("superstep / k_ladder > 1: a super-step "
                   "scans one-token decode steps, and this family has none "
                   "(a block step already commits block_length tokens a "
                   "dispatch)")
    if engine_config.decode_overlap:
        why.append("decode_overlap: the device-fed twin feeds a step the "
                   "previous step's last sampled token; a block step's input "
                   "is a block of mask tokens, not a token")
    if engine_config.spec_decode:
        why.append("spec_decode: drafts are verified causally inside a "
                   "chunk; a block is bidirectional inside itself")
    if engine_config.prefix_cache or tiers:
        why.append("prefix_cache / KV tiers: a cached page is valid under "
                   "the block-causal mask only at block-aligned boundaries, "
                   "which the prefix index has not been held to yet")
    if engine_config.sp_impl != "none":
        why.append(f"sp_impl={engine_config.sp_impl!r}: the sequence-parallel "
                   "attention paths have no block-causal mask")
    bad = [n for n in (engine_config.page_size, engine_config.max_seq_len,
                       *engine_config.prefill_buckets) if n % Bl]
    if bad:
        why.append(f"page_size, max_seq_len and every prefill bucket must be "
                   f"multiples of block_length {Bl} (a block may not "
                   f"straddle a page or a chunk): {bad} are not")
    return why


# ---------------------------------------------------------------- forward

def _qkv(layer: dict[str, Any], config: SdarConfig, x: jax.Array,
         positions: jax.Array):
    """QK-normed, rotated projections of x [B, S, D] at positions [B, S]:
    q [B, S, H, hd], k / v [B, S, KV, hd]."""
    c = config
    B, S, _ = x.shape
    q = qmm(x, layer["wq"]).reshape(B, S, c.n_heads, c.head_dim)
    k = qmm(x, layer["wk"]).reshape(B, S, c.n_kv_heads, c.head_dim)
    v = qmm(x, layer["wv"]).reshape(B, S, c.n_kv_heads, c.head_dim)
    q = rms_norm(q, layer["q_norm"], c.norm_eps)
    k = rms_norm(k, layer["k_norm"], c.norm_eps)
    return (apply_rope(q, positions, c.rope_theta),
            apply_rope(k, positions, c.rope_theta), v)


def _trunk(params: dict[str, Any], config: SdarConfig, tokens: jax.Array,
           positions: jax.Array, kv: PagedKVState, slot_ids: jax.Array,
           attend, mesh) -> tuple[jax.Array, PagedKVState]:
    """Every layer over a [B, S] block at ABSOLUTE positions (-1: padding,
    neither written nor read). ``attend(q, k, v, kv, idx) -> [B, S, H, hd]``
    is the step's attention, called after the block's K/V is written.
    -> (final-normed hidden [B, S, D], kv)."""
    c = config
    x = embed_rows(params["embed"], tokens)
    valid, safe = positions >= 0, jnp.maximum(positions, 0)
    for idx, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k, v = _qkv(layer, c, h, safe)
        kv = write_prefill_kv(kv, idx, k, v, slot_ids, safe, valid)
        out = attend(q, k, v, kv, idx)
        x = x + qmm(out.reshape(*out.shape[:2], -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], c.norm_eps)
        x = x + _ffn_block(layer, c, h, mesh, valid)
    return rms_norm(x, params["final_norm"], c.norm_eps), kv


def _logits(params: dict[str, Any], x: jax.Array,
            last_idx: jax.Array | None, head: bool) -> jax.Array | None:
    """The head over x [B, S, D], over each row's ``last_idx`` alone, or not
    at all (a serving prefill: every position it ran is known)."""
    if not head:
        return None
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]
    return lm_logits(params, x)


def prefill(params: dict[str, Any], config: SdarConfig, tokens: jax.Array,
            positions: jax.Array, kv: PagedKVState, slot_ids: jax.Array,
            attn_impl: str = "reference", mesh=None,
            last_idx: jax.Array | None = None, head: bool = True
            ) -> tuple[jax.Array | None, PagedKVState]:
    """A prompt inside one bucket, from position 0, under the block-causal
    mask; arguments as ``models.llama.prefill``. The logits (all positions,
    or ``last_idx``'s) are those positions' OWN distributions; ``head=False``
    computes none."""
    valid = positions >= 0

    def attend(q, k, v, kv, idx):
        return causal_attention(q, k, v, valid, impl=attn_impl, mesh=mesh,
                                mask_block=config.block_length)

    x, kv = _trunk(params, config, tokens, positions, kv, slot_ids, attend,
                   mesh)
    return _logits(params, x, last_idx, head), kv


def prefill_with_history(params: dict[str, Any], config: SdarConfig,
                         tokens: jax.Array, positions: jax.Array,
                         kv: PagedKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None,
                         head: bool = True
                         ) -> tuple[jax.Array | None, PagedKVState]:
    """A [B, S] run of whole blocks at ABSOLUTE positions (-1: padding) after
    whatever the rows' pages hold: a chunk round, or one pass of a block step
    (S = block_length). Arguments as ``models.llama.prefill_with_history``;
    the queries mask on their block's last position."""
    c = config
    B, S = tokens.shape
    G = c.n_heads // c.n_kv_heads
    tile = _history_tile(S, G)
    valid = positions >= 0
    # a query masks on its block's last position, but on nothing past the
    # row's last real token: a run may end inside a block (a sequence that
    # does), and what its page holds there was never written
    mask_pos = jnp.minimum(block_last(positions, c.block_length),
                           jnp.max(positions, axis=1, keepdims=True))

    def attend(q, k, v, kv, idx):
        if paged_impl == "pallas":
            from ..ops.paged_attention import paged_chunk_attention_pallas
            tables = kv.block_tables[slot_ids]
            if ctx_pages is not None:
                tables = tables[:, :ctx_pages]
        else:
            keys, values = gather_kv(kv, idx, slot_ids, ctx_pages)
        tiles = []
        for t0 in range(0, S, tile):
            qs = q[:, t0:t0 + tile]
            if paged_impl == "pallas":
                qg = qs.reshape(B, -1, c.n_kv_heads, G, c.head_dim)
                at = paged_chunk_attention_pallas(
                    qg, kv.k_pages, kv.v_pages, tables,
                    mask_pos[:, t0:t0 + tile], layer=idx,
                    k_scales=kv.k_scales, v_scales=kv.v_scales, mesh=mesh)
                at = at.reshape(B, -1, c.n_heads, c.head_dim)
            else:
                at = _history_attention(
                    qs, keys, values,
                    jnp.maximum(mask_pos[:, t0:t0 + tile], 0),
                    valid[:, t0:t0 + tile], c)
            tiles.append(at)
        return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)

    x, kv = _trunk(params, c, tokens, positions, kv, slot_ids, attend, mesh)
    return _logits(params, x, last_idx, head), kv


def block_step(params: dict[str, Any], config: SdarConfig, tokens: jax.Array,
               positions: jax.Array, masked: jax.Array, kv: PagedKVState,
               slot_ids: jax.Array, sampling: SamplingParams, key: jax.Array,
               ctx_pages: int | None = None, paged_impl: str = "gather",
               mesh=None):
    """Fill and commit one block a row: tokens, positions, masked [B, Bl]
    (masked positions hold ``mask_token_id``; an idle row has positions -1 and
    nothing masked); ``sampling``: the rows' parameters [B].
    -> ((tokens [B, Bl] final, denoise passes made, positions the threshold
    branch filled), kv). The module docstring has the rule."""
    c = config
    B, Bl = tokens.shape
    live = positions[:, :1] >= 0
    counts = jnp.asarray(fill_counts(Bl, c.denoising_steps), jnp.int32)
    per_position = SamplingParams(*(jnp.repeat(p, Bl) for p in sampling))

    def forward(block_tokens, kv, head):
        return prefill_with_history(
            params, c, block_tokens, positions, kv, slot_ids,
            ctx_pages=ctx_pages, paged_impl=paged_impl, mesh=mesh, head=head)

    def unfinished(carry):
        _, unfilled, passes, _, _, _ = carry
        return jnp.any(unfilled & live) & (passes < c.denoising_steps)

    def denoise(carry):
        block_tokens, unfilled, passes, by_threshold, key, kv = carry
        key, pass_key = jax.random.split(key)
        logits, kv = forward(block_tokens, kv, True)          # [B, Bl, V]
        x0, confidence = sample_with_confidence(
            logits.reshape(B * Bl, -1), per_position, pass_key)
        fill, by_rule = fill_positions(
            confidence.reshape(B, Bl), unfilled, counts[passes],
            c.confidence_threshold)
        block_tokens = jnp.where(fill, x0.reshape(B, Bl).astype(
            block_tokens.dtype), block_tokens)
        by_threshold = by_threshold + jnp.sum(
            fill & by_rule[:, None] & live, dtype=jnp.int32)
        return (block_tokens, unfilled & ~fill, passes + 1, by_threshold, key,
                kv)

    zero = jnp.zeros((), jnp.int32)
    block_tokens, _, passes, by_threshold, _, kv = jax.lax.while_loop(
        unfinished, denoise, (tokens, masked, zero, zero, key, kv))
    # the commit: the final tokens' K/V is what later blocks attend to
    _, kv = forward(block_tokens, kv, False)
    return (block_tokens, passes, by_threshold), kv
