"""Plain reference of the block-diffusion decoder (SDAR's layer stack and its
generation rule), for the comparison that decides ``correct`` and for the CPU
tests. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernel, no batching,
nothing imported from ``mcp_context_forge_tpu``; the engine's own weight tree,
int8 leaves ``{"q", "s"}`` dequantised a layer (an expert) at a time.

**The layer equations** (``config.json`` of SDAR-30B-A3B-Chat: 48 layers,
hidden 2048, 32 query heads and 4 kv heads of 128, ``rope_theta`` 1e6, no
sliding window, no biases, ``rms_norm_eps`` 1e-6, 128 experts of width 768
top-8 with ``norm_topk_prob``, every layer an expert layer, vocabulary 151936
untied). For tokens ``t_i`` at positions ``p_i``, ``x = E[t]``, a layer:

1. ``h = RMSNorm(x; w_attn)``; ``q = h Wq`` as [T, H, hd], ``k = h Wk``,
   ``v = h Wv`` as [T, KV, hd].
2. ``q = RMSNorm_hd(q; w_qn)``, ``k = RMSNorm_hd(k; w_kn)``: over ``head_dim``,
   one weight vector shared by all heads, BEFORE the rotation (assumed:
   ``sdar_moe`` is the Qwen3-MoE trunk, whose attention has these two norms).
3. RoPE (rotate-half, theta 1e6) on q and k at the TRUE positions.
4. ``a_ij = softmax_j(q_i . k_j / sqrt(hd))`` over the keys with
   ``p_j // Bl <= p_i // Bl`` (block-causal: a query sees its whole block and
   all before it); query head g reads kv head ``g // (H / KV)``;
   ``x = x + o Wo``.
5. ``h = RMSNorm(x; w_ffn)``; ``s = softmax(h Wr)`` over all experts; the
   ``top_k`` largest, renormalised to sum 1; ``y = sum_e s'_e W_down,e
   (silu(W_gate,e h) * W_up,e h)``; ``x = x + y``.
6. After the last layer ``RMSNorm``, then the head. ``logits_i`` are the
   distribution of token i ITSELF: no shift (assumed: as the published
   generation code indexes them).

Deliberately NOT the program's formulation: attention is one ``[T, T]`` score
matrix a head with the mask written as a comparison of block indices (the
program rounds the query's position up and compares positions); the expert
FFN loops over the experts and, for each, over nothing but the tokens' gate
column, dequantising one expert at a time (the program's two formulations are
a gate-masked ``lax.scan`` over stacked weights and a row-block kernel).

**Generation** (``generate``; the family's published defaults:
``block_length`` 4, ``denoising_steps`` 4, ``low_confidence_dynamic``,
threshold 0.9). The prompt and everything committed are known. A block's
unknown positions hold the mask token and a FLAG (a sampled token may equal
the mask id). A denoise pass is a full forward over everything up to the
block's end; at each flagged position ``x0 = argmax(logits)`` and its
confidence is ``softmax(logits)[x0]`` (temperature 0). Pass s fills every
flagged position with confidence above the threshold if those are at least
``n_s`` (``Bl // steps``, the remainder one each to the earliest passes), else
the ``n_s`` flagged positions of highest confidence. Passes repeat until no
position is flagged; the block's tokens are then emitted, cut at
``max_tokens``. Departures from the published loop: ties between equal
confidences go to the LOWER position (``torch.topk`` leaves them open), and no
last forward pass is made for the K/V, since nothing is cached here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attention_half(x, layer, *, cfg):
    """x [T, D] -> (x after the attention sublayer, the FFN's normed input)."""
    H, KV, hd, Bl, eps, theta = cfg
    T = x.shape[0]
    h = _rms(x, layer["attn_norm"], eps)
    q = _rms((h @ dequant(layer["wq"])).reshape(T, H, hd), layer["q_norm"], eps)
    k = _rms((h @ dequant(layer["wk"])).reshape(T, KV, hd), layer["k_norm"], eps)
    v = (h @ dequant(layer["wv"])).reshape(T, KV, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(F32(hd))
    block = jnp.arange(T) // Bl
    seen = block[None, :] <= block[:, None]              # [query, key]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    x = x + out.reshape(T, H * hd) @ dequant(layer["wo"])
    return x, _rms(x, layer["ffn_norm"], eps)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _experts(h, layer, *, top_k):
    """h [T, D] -> (the routed FFN's output [T, D], each token's routing
    margin: the probability by which its last chosen expert beat the first
    one left out)."""
    probs = jax.nn.softmax(h @ dequant(layer["router"]), axis=-1)     # [T, E]
    ranked, chosen = jax.lax.top_k(probs, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    kept = ranked[:, :top_k] / jnp.sum(ranked[:, :top_k], axis=-1, keepdims=True)
    E = probs.shape[-1]
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], chosen[:, :top_k]].set(kept)

    def one(e, out):
        pick = lambda w: jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, 0, keepdims=False), w)
        # a sliced expert stack [D, F] / [F, D] has its scale on the out axis
        w1, w3, w2 = (dequant(pick(layer[name])) for name in ("w1", "w3", "w2"))
        y = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
        return out + jax.lax.dynamic_index_in_dim(gates, e, 1) * y

    return jax.lax.fori_loop(0, E, one, jnp.zeros_like(h)), margin


@jax.jit
def _embed(embed, tokens):
    if isinstance(embed, dict):     # per-row scales
        return embed["q"][tokens].astype(F32) * embed["s"][tokens].astype(F32)[:, None]
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    return _rms(x, final_norm, eps) @ dequant(head)


def forward(params, config, tokens, positions):
    """Logits [len(positions), vocab] of ONE full forward pass over ``tokens``
    (at positions 0..) under the block-causal mask, at the stated
    ``positions``, each the distribution of that position's own token; and the
    smallest routing margin over the layers at each of them. A last block
    that ``tokens`` ends inside sees the tokens that exist.

    ``config`` needs ``n_heads, n_kv_heads, head_dim, rope_theta, norm_eps,
    moe_top_k, block_length``; ``params`` is the engine's tree."""
    cfg = (config.n_heads, config.n_kv_heads, config.head_dim,
           int(config.block_length), float(config.norm_eps),
           float(config.rope_theta))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        margins = None
        for layer in params["layers"]:
            x, h = _attention_half(x, layer, cfg=cfg)
            out, margin = _experts(h, layer, top_k=config.moe_top_k)
            x = x + out
            margins = margin if margins is None else jnp.minimum(margins, margin)
        at = jnp.asarray(positions)
        logits = _head(x[at], params["final_norm"], params["lm_head"],
                       eps=float(config.norm_eps))
        return logits, margins[at]


def fill_counts(block_length: int, denoising_steps: int) -> list[int]:
    base, extra = divmod(block_length, denoising_steps)
    return [base + (s < extra) for s in range(denoising_steps)]


def fill_rule(confidence, flagged, count: int, threshold: float):
    """The positions one pass fills: ``confidence``, ``flagged`` over a block's
    positions. Every flagged position above ``threshold`` if those are at
    least ``count``, else the ``count`` flagged positions of highest
    confidence, ties to the lower position. -> (sorted positions, whether the
    threshold branch chose them)."""
    over = [i for i, f in enumerate(flagged) if f and confidence[i] > threshold]
    if over and len(over) >= count:
        return over, True
    ranked = sorted((i for i, f in enumerate(flagged) if f),
                    key=lambda i: (-confidence[i], i))
    return sorted(ranked[:count]), False


def generate(params, config, prompt, max_tokens: int, stop_ids=(),
             passes_out: list | None = None):
    """Greedy generation by diffusion over blocks from ``prompt`` (token ids):
    the tokens emitted, cut at ``max_tokens`` or after the first of
    ``stop_ids``. ``passes_out`` (optional) receives, a pass, ``(the sequence
    the pass ran over, its block's flags, the block's logits [Bl, V])``."""
    Bl = int(config.block_length)
    counts = fill_counts(Bl, int(config.denoising_steps))
    known, emitted = list(prompt), []
    while len(emitted) < max_tokens:
        start = len(known) - len(known) % Bl
        have = len(known) - start
        block = known[start:] + [int(config.mask_token_id)] * (Bl - have)
        flagged = [False] * have + [True] * (Bl - have)
        for s in range(int(config.denoising_steps)):
            if not any(flagged):
                break
            logits, _ = forward(params, config, known[:start] + block,
                                list(range(start, start + Bl)))
            logits = np.asarray(logits, np.float64)
            if passes_out is not None:
                passes_out.append((known[:start] + block, list(flagged), logits))
            x0 = logits.argmax(-1)
            shifted = np.exp(logits - logits.max(-1, keepdims=True))
            confidence = (shifted / shifted.sum(-1, keepdims=True))[
                np.arange(Bl), x0]
            fill, _ = fill_rule(confidence, flagged, counts[s],
                                float(config.confidence_threshold))
            for i in fill:
                block[i], flagged[i] = int(x0[i]), False
        for token in block[have:]:
            known.append(token)
            emitted.append(token)
            if len(emitted) >= max_tokens or token in stop_ids:
                return emitted
    return emitted
