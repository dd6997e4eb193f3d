"""Hybrid decoder whose layers differ in their MIXER and all route (the
``solar_open2`` layer stack), functional like ``models/llama.py``. It calls
what the other families have: the GQA trunk's cache writers, flash / paged
kernels and references, the delta-rule plumbing and the full-attention reads
of ``models/olmo_hybrid.py``, ``models/afmoe.py``'s output gate,
``models/deepseek.py``'s router and ``models/llama.py``'s two expert
formulations.

A layer's KIND names both parts: ``gqa.experts`` | ``kda.experts``
(``layer_kind``). With ``RMS_x`` an RMSNorm of its own weight, pre-norm
residual blocks and no rotary embedding anywhere:

    a = RMS_mixer(x)
    GQA layer:  q = a W_q (H heads), k, v = a W_k, a W_v (KV heads);
                o = softmax(q k^T / sqrt(hd)) v over every earlier key;
                x = x + (o * sigmoid(a W_g)) W_o          (``afmoe``'s gate)
    KDA layer:  q, k, v = SiLU(conv(a W_q)), SiLU(conv(a W_k)), SiLU(conv(a W_v))
                (causal depthwise, ``conv_kernel`` taps, no bias); a head's
                q = L2norm(q) d_k^-0.5, k = L2norm(k);
                g = -exp(A_log_h) softplus((a W_f1) W_f2 + dt_bias)   [H, d_k]
                beta = 2 sigmoid(a W_b)                               [H]
                S <- Diag(e^g) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
                x = x + (RMS_o(o) * sigmoid((a W_g1) W_g2)) W_o
    m = RMS_ffn(x)
    s = sigmoid(m W_r) float32; ids = top-k of (s + b);
    w = s[ids] / sum s[ids] * routed_scaling_factor        (``deepseek.route``)
    x = x + sum_k w_k Expert_ids_k(m) + Shared(m)

and an untied head over ``RMS_f(x)``. The recurrence is
``ops/gated_delta.py``'s with a decay a key CHANNEL (Kimi Delta Attention); a
sequence keeps ``S`` (float32) and the last ``conv_kernel - 1``
pre-convolution inputs in the cache's per-sequence pools at the row its slot
owns, a GQA layer its K and V in pages under the block table
(``kv/paged_cache.py: HybridKVState``, as ``models/olmo_hybrid.py``, whose
rules for padding, idle rows, the trash row and a fresh row hold here word for
word: the code is the same).

**A share of the experts.** The router scores all ``n_experts``; this engine
holds ``experts_held`` of them. A pair whose expert is held elsewhere gets no
row of the plan and a zero gate in the scan (``llama.routed_experts``), and
adds nothing here: the chip of an expert-parallel deployment that holds it
would. Nothing stands in for the absent chips. Which formulation a step takes
is ``models/afmoe.py``'s rule of weight passes (``expert_path``, the engine's
name for it here too), which counts the passes over the HELD experts and an
expert's share of the pairs over all of them: at 32 decode rows of 320 x
top-8 with 40 held, min(32, 40 + 2) = 32 passes against the scan's 2 x 40; a
1024-token chunk round, min(1024, 40 + 32) = 72 against 80 (26 rows an expert
in blocks of 32); one row, 1 against 80: every step of the benchmark's cell
is grouped.

The residual stream, the decay, ``beta`` and the state are float32; every
projection reads the normed stream in the compute dtype.

Every step function also returns a float32 vector of counts (``STEP_AUX``,
laid out as ``models/olmo_hybrid.py``'s): tokens through expert layers,
token-expert pairs on HELD experts, 0, the rows, the live state rows and the
real (unpadded) tokens scanned.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from . import olmo_hybrid
from .afmoe import ROUTER_BIAS_SCALE, expert_path, gated_output
from .configs import SolarOpen2Config
from .deepseek import route
from .llama import _dense, _ffn, lm_logits, rms_norm, routed_experts
from .olmo_hybrid import delta_body as olmo_delta_body
from .olmo_hybrid import (conv_qkv, decode_attend, delta_impl, delta_rule,
                          history_attend, init_keys, init_trunk,  # noqa: F401 (family names)
                          prefill_impl, prefill_unit, state_rows)
from ..kv.paged_cache import (HybridKVState, init_kv_state,  # noqa: F401 (family names)
                              kv_logical, kv_page_bytes, write_decode_kv,
                              write_prefill_kv)
from ..ops.attention import causal_attention, select_paged_attention
from ..quantize import embed_rows, qmm

STEP_AUX = True
STEP_KIND = "token"  # a decode step yields one token a row (models/__init__.py)
KINDS = {"full_attention": "gqa.experts", "linear_attention": "kda.experts"}


# ----------------------------------------------------------------- params

def layer_kind(config: SolarOpen2Config, layer: int) -> str:
    """``<mixer>.<ffn>``: gqa | kda, experts."""
    return KINDS[config.mixer_kind(layer)]


def init_layer(config: SolarOpen2Config, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "kda.experts") -> dict[str, Any]:
    """One layer's random weights; ``kind``'s first half picks the mixer. The
    expert stacks are the HELD experts' alone."""
    c = config
    D, F, E = c.dim, c.moe_ffn_hidden, c.n_held
    k = jax.random.split(key, 20)
    ones = lambda n: jnp.ones((n,), dtype=jnp.float32)
    shared = c.n_shared_experts * F
    layer = {
        "mixer_norm": ones(D), "ffn_norm": ones(D),
        "router": _dense(k[0], (D, c.n_experts), D, jnp.float32),
        "router_bias": ROUTER_BIAS_SCALE * jax.random.normal(
            k[1], (c.n_experts,), dtype=jnp.float32),
        "w1": _dense(k[2], (E, D, F), D, dtype),
        "w3": _dense(k[3], (E, D, F), D, dtype),
        "w2": _dense(k[4], (E, F, D), F, dtype),
        "shared_w1": _dense(k[5], (D, shared), D, dtype),
        "shared_w3": _dense(k[6], (D, shared), D, dtype),
        "shared_w2": _dense(k[7], (shared, D), shared, dtype),
    }
    if kind.startswith("gqa"):
        Q, KV = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        layer.update({
            "wq": _dense(k[8], (D, Q), D, dtype),
            "wk": _dense(k[9], (D, KV), D, dtype),
            "wv": _dense(k[10], (D, KV), D, dtype),
            "wg": _dense(k[11], (D, Q), D, dtype),
            "wo": _dense(k[12], (Q, D), Q, dtype)})
        return layer
    H, dk, dv, R = (c.linear_n_heads, c.linear_key_dim, c.linear_value_dim,
                    c.gate_rank)
    # the published initialisation (the gated delta rule's, a channel where
    # that draws a head): A uniform in (0, 16) a head, dt log-uniform in
    # (0.001, 0.1) a channel through the inverse softplus, so that random
    # weights decay as trained ones do
    A = jax.random.uniform(k[18], (H,), jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(k[19], (H * dk,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    layer.update({
        "wq": _dense(k[8], (D, H * dk), D, dtype),
        "wk": _dense(k[9], (D, H * dk), D, dtype),
        "wv": _dense(k[10], (D, H * dv), D, dtype),
        "wo": _dense(k[11], (H * dv, D), H * dv, dtype),
        "wf_down": _dense(k[12], (D, R), D, dtype),
        "wf_up": _dense(k[13], (R, H * dk), R, dtype),
        "wg_down": _dense(k[14], (D, R), D, dtype),
        "wg_up": _dense(k[15], (R, H * dv), R, dtype),
        "wb": _dense(k[16], (D, H), D, dtype),
        "conv": _dense(k[17], (c.conv_kernel, c.conv_dim), c.conv_kernel,
                       dtype),
        "A_log": jnp.log(A),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": ones(dv)})
    return layer


def init_params(config: SolarOpen2Config, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype,
                                   kind=layer_kind(config, i))
                        for i in range(config.n_layers)]
    return params


def params_logical(config: SolarOpen2Config) -> dict[str, Any]:
    """The trunk's logical names (so ``quantize_tree`` takes the wide
    projections, the GQA gate among them, the shared expert and the expert
    stacks); the norms, the router and its bias, the two low-rank pairs,
    ``W_b``, the convolution, ``A_log`` and ``dt_bias`` stay full precision."""
    ffn = {"mixer_norm": "replicated", "ffn_norm": "replicated",
           "router": "replicated", "router_bias": "replicated",
           "w1": "moe_up", "w3": "moe_up", "w2": "moe_down",
           "shared_w1": "ffn_up", "shared_w3": "ffn_up",
           "shared_w2": "ffn_down"}
    wide = {"wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
            "wo": "attn_out"}
    mixers = {
        "full_attention": {**wide, "wg": "attn_qkv"},
        "linear_attention": {**wide, **dict.fromkeys(
            ("wf_down", "wf_up", "wg_down", "wg_up", "wb", "conv", "A_log",
             "dt_bias", "o_norm"), "replicated")}}
    return {"embed": "vocab_in", "final_norm": "replicated",
            "lm_head": "vocab_out",
            "layers": [{**ffn, **mixers[config.mixer_kind(i)]}
                       for i in range(config.n_layers)]}


def param_count(config: SolarOpen2Config) -> int:
    """Parameters HELD here: the held experts of every layer."""
    c = config
    D, H, dk, dv, R = (c.dim, c.linear_n_heads, c.linear_key_dim,
                       c.linear_value_dim, c.gate_rank)
    Q, KV = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    ffn = ((c.n_held + c.n_shared_experts) * 3 * D * c.moe_ffn_hidden
           + D * c.n_experts + c.n_experts + 2 * D)
    gqa = 3 * D * Q + 2 * D * KV
    kda = (2 * D * H * dk + 2 * D * H * dv + 2 * D * R + R * H * (dk + dv)
           + D * H + c.conv_kernel * c.conv_dim + H + H * dk + dv)
    n_gqa = len(c.layers_of("full_attention"))
    return (2 * c.vocab_size * D + D + c.n_layers * ffn + n_gqa * gqa
            + (c.n_layers - n_gqa) * kda)


# ------------------------------------------------ what the engine looks up

def paged_impl(mesh, config: SolarOpen2Config, kv: HybridKVState) -> str:
    return select_paged_attention(mesh, config.head_dim, kv.page_size,
                                  config.n_kv_heads, False)


def delta_body(config: SolarOpen2Config, mesh, seq: int) -> str | None:
    """``olmo_hybrid.delta_body`` for the channel form of the rule."""
    return olmo_delta_body(config, mesh, seq, channel=True)


def refusals(config: SolarOpen2Config, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings this family cannot serve yet: ``olmo_hybrid``'s, for
    its reasons (the state pool and the kernels are the same)."""
    return olmo_hybrid.refusals(config, engine_config, mesh, tiers)


# ---------------------------------------------------------------- forward

def _gqa_mixer(layer: dict[str, Any], config: SolarOpen2Config, ordinal: int,
               a: jax.Array, kv: HybridKVState, attend
               ) -> tuple[jax.Array, HybridKVState]:
    """The gated GQA mixer of the normed a [B, S, D]: un-normed, un-rotated
    projections; ``attend(ordinal, q, k, v, kv) -> ([B, S, H, hd], kv)`` is
    the step's write and read of the layer's pages."""
    c = config
    B, S, _ = a.shape
    q = qmm(a, layer["wq"]).reshape(B, S, c.n_heads, c.head_dim)
    k = qmm(a, layer["wk"]).reshape(B, S, c.n_kv_heads, c.head_dim)
    v = qmm(a, layer["wv"]).reshape(B, S, c.n_kv_heads, c.head_dim)
    out, kv = attend(ordinal, q, k, v, kv)
    return gated_output(layer, a, out), kv


def _kda_mixer(layer: dict[str, Any], config: SolarOpen2Config, ordinal: int,
               a: jax.Array, stream: jax.Array, valid: jax.Array,
               rows: jax.Array, counts: jax.Array, fresh: jax.Array,
               kv: HybridKVState, impl: str
               ) -> tuple[jax.Array, HybridKVState]:
    """The Kimi-Delta-Attention mixer of the normed a [B, S, D] (``stream``:
    the same in float32, which the decay and beta read) over the rows' stored
    state; the other arguments as ``olmo_hybrid._linear_mixer``'s."""
    c = config
    B, S, _ = a.shape
    H, dk, dv = c.linear_n_heads, c.linear_key_dim, c.linear_value_dim
    q, k, v, kv = conv_qkv(layer, c, ordinal, a, rows, counts, fresh, kv)
    # a channel's decay is exp(-A softplus(f)) with A up to 16: float32 from
    # the float32 stream, as the scalar form's (olmo_hybrid._linear_mixer)
    hi, f32 = jax.lax.Precision.HIGHEST, jnp.float32
    f = jnp.dot(jnp.dot(stream, layer["wf_down"].astype(f32), precision=hi),
                layer["wf_up"].astype(f32), precision=hi)
    g = (-jnp.exp(layer["A_log"])[:, None] * jax.nn.softplus(
        f + layer["dt_bias"]).reshape(B, S, H, dk))
    b = jnp.dot(stream, layer["wb"].astype(f32), precision=hi)
    beta = jax.nn.sigmoid(b) * (2.0 if c.allow_neg_eigval else 1.0)
    o, kv = delta_rule(q, k, v, g, beta, valid, rows, counts, fresh, kv,
                       ordinal, impl)
    z = qmm(qmm(a, layer["wg_down"]), layer["wg_up"])
    gated = (rms_norm(o, layer["o_norm"], c.norm_eps)
             * jax.nn.sigmoid(z.reshape(B, S, H, dv).astype(f32)))
    return qmm(gated.reshape(B, S, H * dv).astype(a.dtype), layer["wo"]), kv


@partial(jax.jit, static_argnames=("config", "mesh"))
def _expert_ffn(layer: dict[str, Any], config: SolarOpen2Config, x: jax.Array,
                valid: jax.Array, mesh) -> tuple[jax.Array, jax.Array]:
    """The HELD routed experts' part (the sigmoid router's choices over all
    of them into the trunk's two formulations, by the family's rule) + the
    shared expert. x [B, S, D]; valid [B, S] -> ([B, S, D], pairs of valid
    tokens on held experts). Jitted, so a step program traces and lowers it
    once a shape (``afmoe._expert_ffn``: why)."""
    c = config
    flat = x.reshape(-1, x.shape[-1])
    ids, weights, _ = route(layer, c, flat)
    lo, hi = c.experts_held
    here = (ids >= lo) & (ids < hi) & valid.reshape(-1, 1)
    routed = routed_experts({k: layer[k] for k in ("w1", "w3", "w2")}, c,
                            flat, ids, weights, mesh, valid, rule=expert_path,
                            held=c.experts_held)
    shared = _ffn({"w1": layer["shared_w1"], "w3": layer["shared_w3"],
                   "w2": layer["shared_w2"]}, flat, c.hidden_act)
    return (routed + shared).reshape(x.shape), jnp.sum(here.astype(jnp.float32))


def _trunk(params: dict[str, Any], config: SolarOpen2Config,
           tokens: jax.Array, positions: jax.Array, valid: jax.Array,
           kv: HybridKVState, slot_ids: jax.Array, attend, mesh
           ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """Every layer over a [B, S] block. positions: absolute, -1 for padding
    (a prefix of each row is real); valid [B, S]: tokens whose cache entries
    are kept. ``attend``: :func:`_gqa_mixer`'s. -> (final-normed hidden
    [B, S, D], kv, aux)."""
    c, f32 = config, jnp.float32
    h = embed_rows(params["embed"], tokens)
    act, x = h.dtype, h.astype(f32)      # the residual stream is float32
    rows, counts, fresh = state_rows(valid, positions, kv, slot_ids)
    impl = delta_impl(mesh, c)
    ordinal = dict.fromkeys(KINDS, 0)
    pairs = jnp.zeros((), f32)
    for idx, layer in enumerate(params["layers"]):
        mixer = c.mixer_kind(idx)
        stream = rms_norm(x, layer["mixer_norm"], c.norm_eps)
        if mixer == "full_attention":
            mixed, kv = _gqa_mixer(layer, c, ordinal[mixer],
                                   stream.astype(act), kv, attend)
        else:
            mixed, kv = _kda_mixer(layer, c, ordinal[mixer],
                                   stream.astype(act), stream, valid, rows,
                                   counts, fresh, kv, impl)
        ordinal[mixer] += 1
        x = x + mixed.astype(f32)
        m = rms_norm(x, layer["ffn_norm"], c.norm_eps).astype(act)
        f, here = _expert_ffn(layer, c, m, valid, mesh)
        x, pairs = x + f.astype(f32), pairs + here
    scanned = jnp.sum(counts).astype(f32)
    aux = jnp.stack([scanned * c.n_layers, pairs, jnp.zeros((), f32),
                     jnp.sum((counts > 0).astype(f32)),
                     jnp.sum((rows > 0).astype(f32)), scanned])
    return rms_norm(x, params["final_norm"], c.norm_eps).astype(act), kv, aux


def _logits(params: dict[str, Any], x: jax.Array,
            last_idx: jax.Array | None) -> jax.Array:
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]
    return lm_logits(params, x)


def prefill(params: dict[str, Any], config: SolarOpen2Config,
            tokens: jax.Array, positions: jax.Array, kv: HybridKVState,
            slot_ids: jax.Array, attn_impl: str = "reference", mesh=None,
            last_idx: jax.Array | None = None
            ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A prompt inside one bucket, from position 0; arguments as
    ``models.llama.prefill``. -> (logits, kv, aux)."""
    valid, safe = positions >= 0, jnp.maximum(positions, 0)

    def attend(ordinal, q, k, v, kv):
        kv = write_prefill_kv(kv, ordinal, k, v, slot_ids, safe, valid)
        return causal_attention(q, k, v, valid, impl=attn_impl, mesh=mesh), kv

    x, kv, aux = _trunk(params, config, tokens, positions, valid, kv,
                        slot_ids, attend, mesh)
    return _logits(params, x, last_idx), kv, aux


def prefill_with_history(params: dict[str, Any], config: SolarOpen2Config,
                         tokens: jax.Array, positions: jax.Array,
                         kv: HybridKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None
                         ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A [B, S] block of prompt tokens at ABSOLUTE positions (-1 = padding)
    after whatever the rows already hold: a chunk round. A GQA layer attends
    over the rows' pages (``ctx_pages`` bounds only them); a KDA layer
    continues from the row's stored state and tail, or from zero where the
    row's first position is 0. Arguments as
    ``models.llama.prefill_with_history``. -> (logits, kv, aux)."""
    valid, safe = positions >= 0, jnp.maximum(positions, 0)

    def attend(ordinal, q, k, v, kv):
        kv = write_prefill_kv(kv, ordinal, k, v, slot_ids, safe, valid)
        return history_attend(config, ordinal, q, kv, slot_ids, positions,
                              ctx_pages, paged_impl == "pallas", mesh), kv

    x, kv, aux = _trunk(params, config, tokens, positions, valid, kv,
                        slot_ids, attend, mesh)
    return _logits(params, x, last_idx), kv, aux


def decode_step(params: dict[str, Any], config: SolarOpen2Config,
                tokens: jax.Array, positions: jax.Array, kv: HybridKVState,
                slot_ids: jax.Array, seq_lens: jax.Array,
                ctx_pages: int | None = None,
                write_mask: jax.Array | None = None,
                paged_impl: str = "gather", mesh=None
                ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """One token a slot; arguments as ``models.llama.decode_step``. A row that
    ``write_mask`` leaves out (idle, mid-chunk-prefill, frozen) writes the
    trash page and reads and writes the trash state row. -> (logits [B, V],
    kv, aux)."""
    c = config
    B = tokens.shape[0]
    valid = (jnp.ones((B,), dtype=bool) if write_mask is None else write_mask)

    def attend(ordinal, q, k, v, kv):
        kv = write_decode_kv(kv, ordinal, k[:, 0], v[:, 0], slot_ids,
                             positions, valid=write_mask)
        out = decode_attend(c, ordinal, q, kv, slot_ids, seq_lens, ctx_pages,
                            paged_impl, mesh)
        return out.reshape(B, 1, c.n_heads, c.head_dim), kv

    # a decode token never starts a sequence: its position is at least 1
    x, kv, aux = _trunk(params, c, tokens[:, None],
                        jnp.where(valid, jnp.maximum(positions, 1), -1)[:, None],
                        valid[:, None], kv, slot_ids, attend, mesh)
    return lm_logits(params, x[:, 0]), kv, aux
