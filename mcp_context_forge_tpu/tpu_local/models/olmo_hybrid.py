"""Hybrid decoder whose layers differ in their MIXER (the Olmo-Hybrid layer
stack), functional like ``models/llama.py``.

Both kinds of layer are Olmo-3's reordered-norm block: ``x += RMSNorm(mixer(x))``,
``x += RMSNorm(MLP(x))`` with a SwiGLU MLP; a final RMSNorm and an untied head.

- **Full attention** (every ``full_attention_interval``-th layer): ``q =
  RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the WHOLE projection (QK-norm,
  before the split into heads), ``v = x W_v``; causal softmax attention at
  ``head_dim^-0.5`` and NO rotary embedding (position comes from the recurrent
  layers). It is the GQA trunk's attention: the same cache writers, the same
  flash / paged kernels and references (``models/llama.py``,
  ``ops/paged_attention.py``), over K/V pages that only these layers hold.
- **Linear attention** (gated delta rule, ``ops/gated_delta.py``): ``q, k, v``
  projections pass a causal depthwise convolution over time (``conv_kernel``
  taps, no bias) and SiLU; a head's ``q = L2norm(q) d_k^-0.5``, ``k =
  L2norm(k)``; ``g = -exp(A_log) softplus(x W_a + dt_bias)`` and ``beta = 2
  sigmoid(x W_b)`` in float32; ``S <- e^g S + beta k (v - e^g S^T k)^T``, ``o =
  S^T q``; output ``W_o (RMSNorm(o) * SiLU(x W_g))``. A sequence keeps ``S``
  (float32) and the last ``conv_kernel - 1`` pre-convolution inputs in the
  cache's per-sequence pools (``kv/paged_cache.py: HybridKVState``), at the
  row its slot owns.

**Padding and idle rows.** A prefill bucket's padding is not scanned: the
recurrence takes the identity step there (``g = 0, beta = 0``; the kernel does
not visit it at all) and the convolution's tail is the last REAL inputs. A
batch's padding rows, idle decode rows and rows masked by ``write_mask`` read
and write state row 0, the trash row. A row whose first token has position 0
starts from a zero state and a zero tail by a flag in the program: what the
row's last tenant left (or a stale overlapped decode step wrote) is never read.

Every step function also returns a float32 vector of counts (``STEP_AUX``, laid
out as ``models/deepseek.py``'s with two more entries): zeros for the expert
and selector counts, the rows, then the live state rows and the real
(unpadded) tokens scanned.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from . import llama
from .configs import OlmoHybridConfig
from .llama import (_dense, _ffn, _history_attention, _history_tile,
                    _paged_decode_attention, lm_logits, rms_norm)
from ..kv.paged_cache import (HybridKVState, gather_kv, init_kv_state,  # noqa: F401 (family names)
                              kv_logical, kv_page_bytes, write_decode_kv,
                              write_prefill_kv)
from ..ops import gated_delta
from ..ops.attention import (causal_attention, on_tpu, select_paged_attention,
                             select_prefill_attention)
from ..quantize import embed_rows, qmm

STEP_AUX = True
STEP_KIND = "token"  # a decode step yields one token a row (models/__init__.py)
L2_EPS = 1e-6
# Random gate projections W_a, W_b are drawn this much smaller than the other
# dense weights. The reordered-norm block feeds the mixer the UN-normed
# residual stream (RMS up to sqrt(63) = 8 by the last layer), so at 1/sqrt(dim)
# the gates' pre-activations would be N(0, 64): beta = 2 sigmoid(b) pinned at 0
# or 2 and the decay at 0 or 1. At beta = 2 the delta rule is a reflection that
# never forgets a rounding error (on the chip a bfloat16 state and the
# bfloat16 program both read 0.44 there: PERF.md, PR 33). With O(1)
# pre-activations, as the published initialisation has them over normed
# inputs, beta stays inside (0, 2) and the rule contracts.
GATE_INIT_SCALE = 0.125
_PALLAS_QUERY_TILE = 128


# ----------------------------------------------------------------- params

def layer_kind(config: OlmoHybridConfig, layer: int) -> str:
    """``linear_attention`` | ``full_attention``: the layer's mixer."""
    return config.mixer_kind(layer)


def init_layer(config: OlmoHybridConfig, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "linear_attention") -> dict[str, Any]:
    """One layer's random weights; ``kind`` is its mixer."""
    c = config
    D, F = c.dim, c.ffn_hidden
    k = jax.random.split(key, 12)
    ones = lambda n: jnp.ones((n,), dtype=jnp.float32)
    layer = {
        "mixer_norm": ones(D), "ffn_norm": ones(D),
        "w1": _dense(k[0], (D, F), D, dtype),
        "w3": _dense(k[1], (D, F), D, dtype),
        "w2": _dense(k[2], (F, D), F, dtype),
    }
    if kind == "full_attention":
        Q, KV = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        layer.update({
            "wq": _dense(k[3], (D, Q), D, dtype),
            "wk": _dense(k[4], (D, KV), D, dtype),
            "wv": _dense(k[5], (D, KV), D, dtype),
            "wo": _dense(k[6], (Q, D), Q, dtype),
            "q_norm": ones(Q), "k_norm": ones(KV)})
        return layer
    H, dk, dv = c.linear_n_heads, c.linear_key_dim, c.linear_value_dim
    # the published gated-delta-rule initialisation: A uniform in (0, 16), dt
    # log-uniform in (0.001, 0.1) through the inverse softplus, so that
    # random weights decay as trained ones do
    A = jax.random.uniform(k[10], (H,), jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(k[11], (H,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    layer.update({
        "wq": _dense(k[3], (D, H * dk), D, dtype),
        "wk": _dense(k[4], (D, H * dk), D, dtype),
        "wv": _dense(k[5], (D, H * dv), D, dtype),
        "wg": _dense(k[6], (D, H * dv), D, dtype),
        "wo": _dense(k[7], (H * dv, D), H * dv, dtype),
        "wa": _dense(k[8], (D, H), D, dtype) * GATE_INIT_SCALE,
        "wb": _dense(k[9], (D, H), D, dtype) * GATE_INIT_SCALE,
        "conv": _dense(jax.random.fold_in(key, 99),
                       (c.conv_kernel, c.conv_dim), c.conv_kernel, dtype),
        "A_log": jnp.log(A),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": ones(dv)})
    return layer


def init_trunk(config: OlmoHybridConfig, embed_key: jax.Array,
               head_key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    return {
        "embed": _dense(embed_key, (config.vocab_size, config.dim),
                        config.dim, dtype),
        "final_norm": jnp.ones((config.dim,), dtype=jnp.float32),
        "lm_head": _dense(head_key, (config.dim, config.vocab_size),
                          config.dim, dtype),
    }


def init_keys(config: OlmoHybridConfig, key: jax.Array) -> jax.Array:
    """[n_layers + 2] keys: one per layer, then the embedding's and the head's."""
    return jax.random.split(key, config.n_layers + 2)


def init_params(config: OlmoHybridConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype,
                                   kind=config.mixer_kind(i))
                        for i in range(config.n_layers)]
    return params


def params_logical(config: OlmoHybridConfig) -> dict[str, Any]:
    """Logical names matching init_params' tree. The dense projections carry
    the trunk's names, so ``quantize_tree`` takes them; the gate projections,
    the convolution, norms, ``A_log`` and ``dt_bias`` stay full precision."""
    ffn = {"mixer_norm": "replicated", "ffn_norm": "replicated",
           "w1": "ffn_up", "w3": "ffn_up", "w2": "ffn_down"}
    mixers = {
        "full_attention": {"wq": "attn_qkv", "wk": "attn_qkv",
                           "wv": "attn_qkv", "wo": "attn_out",
                           "q_norm": "replicated", "k_norm": "replicated"},
        "linear_attention": {"wq": "attn_qkv", "wk": "attn_qkv",
                             "wv": "attn_qkv", "wg": "attn_qkv",
                             "wo": "attn_out", "wa": "replicated",
                             "wb": "replicated", "conv": "replicated",
                             "A_log": "replicated", "dt_bias": "replicated",
                             "o_norm": "replicated"}}
    return {
        "embed": "vocab_in", "final_norm": "replicated",
        "lm_head": "vocab_out",
        "layers": [{**ffn, **mixers[config.mixer_kind(i)]}
                   for i in range(config.n_layers)],
    }


def param_count(config: OlmoHybridConfig) -> int:
    c = config
    D = c.dim
    H, dk, dv = c.linear_n_heads, c.linear_key_dim, c.linear_value_dim
    ffn = 3 * D * c.ffn_hidden + 2 * D
    full = (D * (c.n_heads + 2 * c.n_kv_heads) * c.head_dim
            + c.n_heads * c.head_dim * D
            + (c.n_heads + c.n_kv_heads) * c.head_dim)
    linear = (2 * D * H * dk + 3 * D * H * dv + 2 * D * H
              + c.conv_kernel * c.conv_dim + 2 * H + dv)
    n_full = len(c.layers_of("full_attention"))
    return (2 * c.vocab_size * D + D + c.n_layers * ffn + n_full * full
            + (c.n_layers - n_full) * linear)


# ------------------------------------------------ what the engine looks up

def prefill_impl(impl: str, mesh, seq: int, config: OlmoHybridConfig,
                 itemsize: int = 2) -> str:
    return select_prefill_attention(impl, mesh, seq, config.head_dim,
                                    config.n_kv_heads, itemsize)


def prefill_unit(mesh, config: OlmoHybridConfig) -> int:
    """The trunk's (its full-attention layers run the trunk's attention), and
    on a TPU also whole token tiles of the gated delta-rule kernel (the
    ``jax.numpy`` twin pads its own chunks)."""
    unit = llama.prefill_unit(mesh, config)
    return math.lcm(unit, gated_delta._TOKEN_TILE) if on_tpu(mesh) else unit


def paged_impl(mesh, config: OlmoHybridConfig, kv: HybridKVState) -> str:
    return select_paged_attention(mesh, config.head_dim, kv.page_size,
                                  config.kv_pool_heads, False)


def delta_impl(mesh, config) -> str:
    """``pallas`` on a TPU mesh whose head geometry the kernel takes, else the
    ``jax.numpy`` twin (``ops/gated_delta.py``)."""
    aligned = gated_delta.head_group(config.linear_n_heads,
                                     config.linear_value_dim) is not None
    return "pallas" if on_tpu(mesh) and aligned else "jnp"


def delta_body(config, mesh, seq: int, channel: bool = False) -> str | None:
    """Which body of the delta-rule kernel a prefill or a chunk round of
    ``seq`` positions a row traces, from what is visible before tracing:
    ``"chunkwise"`` (whole chunks on the MXU) or ``"walk"`` (token by token),
    ``ops/gated_delta.py``'s rule of shape for the form (``channel``: a decay a
    key channel); None where the ``jax.numpy`` twin runs (:func:`delta_impl`).
    The engine counts its prefill dispatches by the same call."""
    if delta_impl(mesh, config) != "pallas":
        return None
    return gated_delta.chunk_body(seq, config.linear_n_heads,
                                  config.linear_key_dim,
                                  config.linear_value_dim, channel)


def expert_path(config: OlmoHybridConfig, mesh, tokens: int,
                dtype=None) -> None:
    """No routed experts: as the dense trunk answers."""
    return None


def refusals(config: OlmoHybridConfig, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings this family cannot serve yet, each with its reason.
    The engine refuses to build on any of them; nothing falls back."""
    why = []
    if mesh.shape.get("model", 1) > 1:
        why.append("a mesh with more than one device on the model axis: the "
                   "state pool and the gated delta-rule kernels have no "
                   "sharding over it yet")
    if engine_config.prefix_cache:
        why.append("prefix_cache: a hit skips tokens whose recurrent state "
                   "was never stored (needs state snapshots at page "
                   "boundaries)")
    if tiers:
        why.append("KV tiers / fabric / chain export / migration "
                   "(prefix_tiers, a pool's prefix index or tier store): the "
                   "spill payload carries K and V pages, not a sequence's "
                   "recurrent state")
    if engine_config.spec_decode:
        why.append("spec_decode: a rejected draft would have to roll the "
                   "recurrent state back")
    if engine_config.sp_impl != "none":
        why.append(f"sp_impl={engine_config.sp_impl!r}: no sequence-parallel "
                   "scan of the recurrence")
    if engine_config.kv_quant:
        why.append(f"kv_quant={engine_config.kv_quant!r}: the K/V pages of "
                   "the full-attention layers and the float32 state are full "
                   "precision only")
    return why


# ---------------------------------------------------------------- forward

def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def state_rows(valid: jax.Array, positions: jax.Array, kv: HybridKVState,
               slot_ids: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """What a step's delta-rule layers walk, from valid / positions [B, S]:
    (rows [B]: the slots' state row ids, the trash row 0 for a row without a
    real token; counts [B]: real tokens a row; fresh [B]: the row's first
    position is 0, so it starts from a zero state and tail). Shared by every
    family that keeps a recurrent state a sequence (``models/solar_open2.py``)."""
    counts = jnp.sum(valid.astype(jnp.int32), axis=1)
    rows = jnp.where(counts > 0, kv.state_rows[slot_ids], 0)
    return rows, counts, positions[:, 0] <= 0


def conv_with_tail(raw: jax.Array, weight: jax.Array, bias: jax.Array | None,
                   conv_kernel: int, ordinal: int, rows: jax.Array,
                   counts: jax.Array, fresh: jax.Array, kv: HybridKVState):
    """The causal depthwise convolution of raw [B, S, C] over time, CONTINUED
    from the rows' stored tails (zero under ``fresh``) and through SiLU, in
    float32: the sum over ``conv_kernel`` taps of weight [taps, C], plus
    ``bias`` [C] where the family's convolution has one. The rows' tails are
    replaced by the last REAL inputs. -> (SiLU(conv) [B, S, C] float32, kv).
    Every family with a convolution tail calls it (this one and
    ``models/solar_open2.py`` through :func:`conv_qkv`,
    ``models/granite_hybrid.py`` with its bias)."""
    S = raw.shape[1]
    taps = conv_kernel - 1
    tail = jnp.where(fresh[:, None, None], 0,
                     kv.conv_tail[ordinal, rows]).astype(raw.dtype)
    if S == 1:
        # a decode token: the window is the tail's rows and the one input, each
        # a [B, C] term with the batch on the sublanes (a [B, taps + 1, C]
        # concatenation puts four rows in a tile of eight on the chip, and
        # every slice of it inherits that)
        weight = weight.astype(jnp.float32)
        window = [tail[:, i] for i in range(taps)] + [raw[:, 0]]
        conv = sum(weight[i] * window[i].astype(jnp.float32)
                   for i in range(conv_kernel))[:, None]
        # entries counts .. counts + taps - 1 of that window: the tail as it
        # was (counts 0), or moved up by the one real input
        new_tail = jnp.where((counts > 0)[:, None, None],
                             jnp.stack(window[1:], axis=1), tail)
    else:
        # many tokens: equation for equation what it was before the branch
        # (the accepted families' prefill and chunk programs are pinned)
        padded = jnp.concatenate([tail, raw], axis=1)            # [B, taps + S, C]
        weight = weight.astype(jnp.float32)
        conv = sum(weight[i] * padded[:, i:i + S].astype(jnp.float32)
                   for i in range(conv_kernel))
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    conv = jax.nn.silu(conv)
    if S > 1:
        # the last ``taps`` REAL inputs: entries counts .. counts + taps - 1
        last = counts[:, None] + jnp.arange(taps)[None, :]       # [B, taps]
        new_tail = jnp.take_along_axis(padded, last[:, :, None], axis=1)
    kv = kv._replace(conv_tail=kv.conv_tail.at[ordinal, rows].set(
        new_tail.astype(kv.conv_tail.dtype)))
    return conv, kv


def conv_qkv(layer: dict[str, Any], config, ordinal: int, x: jax.Array,
             rows: jax.Array, counts: jax.Array, fresh: jax.Array,
             kv: HybridKVState):
    """A delta-rule mixer's q, k, v of x [B, S, D]: the three projections
    through the causal depthwise convolution (:func:`conv_with_tail`, no
    bias), q and k L2-normed a head. -> (q [B, S, H, dk] scaled by dk^-0.5,
    k, v [B, S, H, dv], kv). ``ordinal``: the layer's index among the linear
    layers."""
    c = config
    B, S, _ = x.shape
    H, dk, dv = c.linear_n_heads, c.linear_key_dim, c.linear_value_dim
    raw = jnp.concatenate([qmm(x, layer["wq"]), qmm(x, layer["wk"]),
                           qmm(x, layer["wv"])], axis=-1)        # [B, S, C]
    conv, kv = conv_with_tail(raw, layer["conv"], None, c.conv_kernel, ordinal,
                              rows, counts, fresh, kv)
    q, k, v = jnp.split(conv, [H * dk, 2 * H * dk], axis=-1)
    q = _l2norm(q.reshape(B, S, H, dk)) * dk ** -0.5
    k = _l2norm(k.reshape(B, S, H, dk))
    return q, k, v.reshape(B, S, H, dv), kv


def delta_rule(q, k, v, g, beta, valid: jax.Array, rows: jax.Array,
               counts: jax.Array, fresh: jax.Array, kv: HybridKVState,
               ordinal: int, impl: str):
    """The recurrence over the rows' stored state (``ops/gated_delta.py``: the
    kernel or its twin by ``impl``), padding tokens made the identity step.
    g [B, S, H], or [B, S, H, dk] for a decay a key channel; beta, valid as
    the step's. -> (o [B, S, H, dv] float32, kv)."""
    on = valid[..., None, None] if g.ndim == 4 else valid[..., None]
    g = jnp.where(on, g, 0.0)                        # identity step on padding
    beta = jnp.where(valid[..., None], beta, 0.0)
    if impl == "pallas":
        o, state = gated_delta.gated_delta_pallas(
            q, k, v, g, beta, kv.state, rows, counts, fresh, layer=ordinal)
    else:
        o, state = gated_delta.gated_delta_reference(
            q, k, v, g, beta, kv.state, rows, fresh, layer=ordinal)
    return o, kv._replace(state=state)


def _linear_mixer(layer: dict[str, Any], config: OlmoHybridConfig,
                  ordinal: int, x: jax.Array, stream: jax.Array,
                  valid: jax.Array, rows: jax.Array, counts: jax.Array,
                  fresh: jax.Array, kv: HybridKVState, impl: str
                  ) -> tuple[jax.Array, HybridKVState]:
    """The gated delta-rule mixer of x [B, S, D] (``stream``: the same hidden
    states in the residual stream's float32, which the gates read) over the
    rows' stored state. valid [B, S]: real tokens, a prefix of each row;
    rows, counts, fresh: :func:`state_rows`. ``ordinal``: the layer's index
    among the linear layers."""
    c = config
    B, S, _ = x.shape
    H, dv = c.linear_n_heads, c.linear_value_dim
    q, k, v, kv = conv_qkv(layer, c, ordinal, x, rows, counts, fresh, kv)
    # the gates in float32 FROM the float32 stream: the decay is exp(-A
    # softplus(a)) with A up to 16 and a = x W_a over an un-normed stream, so
    # the 2^-9 rounding of a bfloat16 x moves a token's decay by several per
    # cent (read on the chip: PERF.md, PR 33); two [D, H] matmuls a layer
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    a = jnp.dot(stream, layer["wa"].astype(f32), precision=hi)
    b = jnp.dot(stream, layer["wb"].astype(f32), precision=hi)
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(a + layer["dt_bias"])
    beta = jax.nn.sigmoid(b) * (2.0 if c.allow_neg_eigval else 1.0)
    o, kv = delta_rule(q, k, v, g, beta, valid, rows, counts, fresh, kv,
                       ordinal, impl)
    z = qmm(x, layer["wg"]).reshape(B, S, H, dv).astype(jnp.float32)
    gated = rms_norm(o, layer["o_norm"], c.norm_eps) * jax.nn.silu(z)
    return qmm(gated.reshape(B, S, H * dv).astype(x.dtype), layer["wo"]), kv


def _qkv(layer: dict[str, Any], config: OlmoHybridConfig, x: jax.Array):
    """QK-normed projections, no rotary: q [B, S, H, hd], k/v [B, S, KV, hd]."""
    c = config
    B, S, _ = x.shape
    q = rms_norm(qmm(x, layer["wq"]), layer["q_norm"], c.norm_eps)
    k = rms_norm(qmm(x, layer["wk"]), layer["k_norm"], c.norm_eps)
    v = qmm(x, layer["wv"])
    return (q.reshape(B, S, c.n_heads, c.head_dim),
            k.reshape(B, S, c.n_kv_heads, c.head_dim),
            v.reshape(B, S, c.n_kv_heads, c.head_dim))


def _pool_heads(config, x: jax.Array, axis: int) -> jax.Array:
    """x with its kv-head axis padded by zeros to the heads a page holds
    (``OlmoHybridConfig.kv_pool_heads``)."""
    extra = config.kv_pool_heads - config.n_kv_heads
    if not extra:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, extra)
    return jnp.pad(x, pad)


def _gather_kv(config, kv: HybridKVState, ordinal: int,
               slot_ids: jax.Array, ctx_pages: int | None):
    """The rows' gathered context without the pool's padding heads."""
    keys, values = gather_kv(kv, ordinal, slot_ids, ctx_pages)
    return keys[:, :, :config.n_kv_heads], values[:, :, :config.n_kv_heads]


def _project_and_write(layer: dict[str, Any], config: OlmoHybridConfig,
                       ordinal: int, x: jax.Array, kv: HybridKVState,
                       slot_ids: jax.Array, positions: jax.Array):
    """A [B, S] block's q, k, v with k and v written into the rows' pages
    (positions -1: padding, not written). -> (q, k, v, kv)."""
    q, k, v = _qkv(layer, config, x)
    kv = write_prefill_kv(kv, ordinal, _pool_heads(config, k, 2),
                          _pool_heads(config, v, 2), slot_ids,
                          jnp.maximum(positions, 0), positions >= 0)
    return q, k, v, kv


def _logits(params: dict[str, Any], x: jax.Array,
            last_idx: jax.Array | None) -> jax.Array:
    """The head over x [B, S, D], or over each row's ``last_idx`` alone."""
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]
    return lm_logits(params, x)


def history_attend(config, ordinal: int, q: jax.Array, kv: HybridKVState,
                   slot_ids: jax.Array, positions: jax.Array,
                   ctx_pages: int | None, use_pallas: bool, mesh) -> jax.Array:
    """The trunk's chunk attention (``models/llama.prefill_with_history``'s
    inner loop) of q [B, S, H, hd] over the rows' pages of the ``ordinal``-th
    full-attention layer, this step's tokens already written; positions
    [B, S] absolute, -1 for padding. (``models/solar_open2.py`` calls it too.)"""
    c = config
    B, S = positions.shape
    G = c.n_heads // c.n_kv_heads
    # the kernel holds every kv head's rows of a tile in VMEM at once: with
    # as many kv heads as query heads a tile is 128 queries (the trunk's
    # _history_tile would ask for 512 at G = 1)
    tile = (min(S, _PALLAS_QUERY_TILE) if use_pallas and G == 1
            else _history_tile(S, G))
    valid, safe = positions >= 0, jnp.maximum(positions, 0)
    if use_pallas:
        from ..ops.paged_attention import paged_chunk_attention_pallas
        tables = kv.block_tables[slot_ids]
        if ctx_pages is not None:
            tables = tables[:, :ctx_pages]
    else:
        keys, values = _gather_kv(c, kv, ordinal, slot_ids, ctx_pages)
    tiles = []
    for t0 in range(0, S, tile):
        qs = q[:, t0:t0 + tile]
        if use_pallas:
            qg = _pool_heads(c, qs.reshape(B, -1, c.n_kv_heads, G, c.head_dim), 2)
            at = paged_chunk_attention_pallas(
                qg, kv.k_pages, kv.v_pages, tables,
                positions[:, t0:t0 + tile], layer=ordinal, mesh=mesh)
            at = at[:, :, :c.n_kv_heads].reshape(B, -1, c.n_heads, c.head_dim)
        else:
            at = _history_attention(qs, keys, values, safe[:, t0:t0 + tile],
                                    valid[:, t0:t0 + tile], c)
        tiles.append(at)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def decode_attend(config, ordinal: int, q: jax.Array, kv: HybridKVState,
                  slot_ids: jax.Array, seq_lens: jax.Array,
                  ctx_pages: int | None, paged_impl: str, mesh) -> jax.Array:
    """One query a row, q [B, 1, H, hd], over the rows' pages of the
    ``ordinal``-th full-attention layer, this step's token already written:
    the paged kernel or the gather reference. -> [B, ..] (H * hd values a
    row). (``models/solar_open2.py`` calls it too.)"""
    c = config
    B = q.shape[0]
    if paged_impl == "pallas":
        from ..ops.paged_attention import paged_decode_attention_pallas
        tables = kv.block_tables[slot_ids]
        if ctx_pages is not None:
            tables = tables[:, :ctx_pages]
        qg = q[:, 0].reshape(B, c.n_kv_heads, c.n_heads // c.n_kv_heads,
                             c.head_dim)
        return paged_decode_attention_pallas(
            _pool_heads(c, qg, 1), kv.k_pages, kv.v_pages, tables,
            seq_lens, layer=ordinal, mesh=mesh)[:, :c.n_kv_heads]
    keys, values = _gather_kv(c, kv, ordinal, slot_ids, ctx_pages)
    return _paged_decode_attention(q[:, 0], keys, values, seq_lens, c)


def _trunk(params: dict[str, Any], config: OlmoHybridConfig,
           tokens: jax.Array, positions: jax.Array, valid: jax.Array,
           kv: HybridKVState, slot_ids: jax.Array, attend, mesh
           ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """Every layer over a [B, S] block. positions: absolute, -1 for padding
    (a prefix of each row is real); valid [B, S]: tokens whose cache entries
    are kept. ``attend(layer, ordinal, x, kv) -> (out, kv)`` is the step's
    full-attention mixer. -> (final-normed hidden [B, S, D], kv, aux)."""
    c = config
    h = embed_rows(params["embed"], tokens)
    # the residual stream is float32 (64 unit-RMS terms add up in it); every
    # matmul reads it in the compute dtype, the gates read it as it is
    act, x = h.dtype, h.astype(jnp.float32)
    rows, counts, fresh = state_rows(valid, positions, kv, slot_ids)
    impl = delta_impl(mesh, c)
    n_full = n_linear = 0
    for idx, layer in enumerate(params["layers"]):
        if c.mixer_kind(idx) == "full_attention":
            mixed, kv = attend(layer, n_full, x.astype(act), kv)
            n_full += 1
        else:
            mixed, kv = _linear_mixer(layer, c, n_linear, x.astype(act), x,
                                      valid, rows, counts, fresh, kv, impl)
            n_linear += 1
        x = x + rms_norm(mixed.astype(jnp.float32), layer["mixer_norm"],
                         c.norm_eps)
        x = x + rms_norm(_ffn(layer, x.astype(act)).astype(jnp.float32),
                         layer["ffn_norm"], c.norm_eps)
    live = jnp.sum((rows > 0).astype(jnp.float32))
    zero = jnp.zeros((), jnp.float32)
    aux = jnp.stack([zero, zero, zero, jnp.sum((counts > 0).astype(jnp.float32)),
                     live, jnp.sum(counts).astype(jnp.float32)])
    return rms_norm(x, params["final_norm"], c.norm_eps).astype(act), kv, aux


def prefill(params: dict[str, Any], config: OlmoHybridConfig,
            tokens: jax.Array, positions: jax.Array, kv: HybridKVState,
            slot_ids: jax.Array, attn_impl: str = "reference", mesh=None,
            last_idx: jax.Array | None = None
            ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A prompt inside one bucket, from position 0; arguments as
    ``models.llama.prefill``. -> (logits, kv, aux)."""
    valid = positions >= 0

    def attend(layer, ordinal, x, kv):
        q, k, v, kv = _project_and_write(layer, config, ordinal, x, kv,
                                         slot_ids, positions)
        out = causal_attention(q, k, v, valid, impl=attn_impl, mesh=mesh)
        return qmm(out.reshape(*out.shape[:2], -1), layer["wo"]), kv

    x, kv, aux = _trunk(params, config, tokens, positions, valid, kv,
                        slot_ids, attend, mesh)
    return _logits(params, x, last_idx), kv, aux


def prefill_with_history(params: dict[str, Any], config: OlmoHybridConfig,
                         tokens: jax.Array, positions: jax.Array,
                         kv: HybridKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None
                         ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A [B, S] block of prompt tokens at ABSOLUTE positions (-1 = padding)
    after whatever the rows already hold: chunk rounds and history suffixes.
    The full-attention layers attend over the rows' pages (``ctx_pages``
    bounds only them); a linear layer continues from the row's stored state
    and tail, or from zero where the row's first position is 0. Arguments as
    ``models.llama.prefill_with_history``. -> (logits, kv, aux)."""
    def attend(layer, ordinal, x, kv):
        q, _, _, kv = _project_and_write(layer, config, ordinal, x, kv,
                                         slot_ids, positions)
        out = history_attend(config, ordinal, q, kv, slot_ids, positions,
                             ctx_pages, paged_impl == "pallas", mesh)
        return qmm(out.reshape(*out.shape[:2], -1), layer["wo"]), kv

    x, kv, aux = _trunk(params, config, tokens, positions, positions >= 0, kv,
                        slot_ids, attend, mesh)
    return _logits(params, x, last_idx), kv, aux


def decode_step(params: dict[str, Any], config: OlmoHybridConfig,
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

    def attend(layer, ordinal, x, kv):
        q, k, v = _qkv(layer, c, x)
        kv = write_decode_kv(kv, ordinal, _pool_heads(c, k[:, 0], 1),
                             _pool_heads(c, v[:, 0], 1), slot_ids, positions,
                             valid=write_mask)
        out = decode_attend(c, ordinal, q, kv, slot_ids, seq_lens,
                            ctx_pages, paged_impl, mesh)
        return qmm(out.reshape(B, 1, -1), layer["wo"]), kv

    # a decode token never starts a sequence: its position is at least 1
    x, kv, aux = _trunk(params, c, tokens[:, None],
                        jnp.where(valid, jnp.maximum(positions, 1), -1)[:, None],
                        valid[:, None], kv, slot_ids, attend, mesh)
    return lm_logits(params, x[:, 0]), kv, aux
