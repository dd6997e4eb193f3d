"""Hybrid decoder whose layers are Mamba-2 state-space mixers or un-rotated
GQA by a LIST of mixer kinds (the ``granitemoehybrid`` layer stack with no
routed experts), functional like ``models/llama.py``. It calls what the other
families have: the GQA trunk's cache writers, flash / paged kernels and
references, ``qmm`` and ``llama._ffn``, and the state-row plumbing, the
convolution-with-a-tail and the full-attention reads of
``models/olmo_hybrid.py``. Its own are the recurrence (``ops/ssd.py``) and the
four multipliers.

With ``RMS_x`` an RMSNorm of its own weight, pre-norm residual blocks and no
rotary embedding anywhere:

    x_0 = embedding_multiplier * Emb(t)
    a = RMS_mixer(x)
    attention:  q = a W_q (H heads), k, v = a W_k, a W_v (KV heads), no bias;
                o = softmax(attention_multiplier q k^T) v over every earlier
                key (NOT ``head_dim^-0.5``: the kernels and references divide
                by the square root of the head width they see, the STORED 128,
                so ``q`` is scaled by ``attention_multiplier * sqrt(128)``
                first)
                mixed = o W_o
    mamba:      z, xBC, dt = a W_z, a W_xbc, a W_dt   (the published fused
                in_proj in its split order z | xBC | dt, held as three)
                xBC = SiLU(conv(xBC) + b_conv)    (causal, depthwise,
                ``conv_kernel`` taps, over x, B and C together)
                x_t [H, P], B_t [N], C_t [N] = split(xBC)       (ONE group)
                dt = softplus(dt + dt_bias), A = -exp(A_log)    [H], float32
                S <- exp(dt A) S + B (dt x)^T;  y = S^T C + D x   (``ops/ssd.py``)
                mixed = (RMS_o(y * SiLU(z))) W_o   (the gate BEFORE the norm,
                the norm over all H P channels)
    x = x + residual_multiplier * mixed
    x = x + residual_multiplier * W_2 (SiLU(m W_1) * m W_3),  m = RMS_ffn(x)
    logits = RMS_f(x) Emb^T / logits_scaling        (the head IS the embedding)

A sequence keeps ``S`` (float32) and the last ``conv_kernel - 1``
pre-convolution inputs in the cache's per-sequence pools at the row its slot
owns, an attention layer its K and V in pages under the block table
(``kv/paged_cache.py: HybridKVState``, as ``models/olmo_hybrid.py``, whose
rules for padding, idle rows, the trash row and a fresh row hold here word for
word: the code is the same; a padding token is ``dt = 0``).

The residual stream, ``dt``, the decay and the state are float32; every other
projection reads the normed stream in the compute dtype. ``W_dt``, the
convolution and its bias, ``A_log``, ``D``, ``dt_bias`` and the norms stay
full precision under int8.

**Heads of 64** are stored and attended over in whole lane tiles
(``GraniteHybridConfig.kv_head_dim``: q, k and v get a zero tail, 64 -> 128;
the scores and the first 64 output lanes are unchanged, and ``q`` is scaled
for a kernel that divides by ``sqrt(128)``): the flash and paged kernels take
them as they stand, at twice the K/V bytes. Left at 64 lanes the selectors'
XLA paths ran, and the chip's compiler kept the half-tile pools compressed
and copied them whole around every layer's write and gather: a decode step of
91 ms (PERF.md section 6, PR 53; section 7: a kernel for narrower heads).

**A decode token is a ROW.** From the in-projections to ``W_o`` a decode
step's one token a sequence is a row of a ``[B, C]`` array with the batch on
the sublanes: ``ssd_step`` takes and returns ``[B, 4096]`` arrays in blocks of
8 rows (``ops/ssd.py``, "ssd_step": why a ``[B, 1, C]`` operand would put
every array around the call in one-sublane tiles), the head scalars are spread
over their lanes by one exact 0/1 product for all rows, the convolution takes
its four taps as four ``[B, C]`` terms (``olmo_hybrid.conv_with_tail`` at
``S == 1``), and the skip, the gate, the norm and the cast run on what the
kernel returns, outside it. ``tests/tpu_local/test_chip_compile.py`` compiles
the step for a described v5e and looks for a narrow tile.

Every step function also returns a float32 vector of counts (``STEP_AUX``,
laid out as ``models/olmo_hybrid.py``'s): zeros for the expert and selector
counts, the rows, then the live state rows and the real tokens scanned.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import llama, olmo_hybrid
from .configs import GraniteHybridConfig
from .llama import _dense, _ffn, rms_norm
from .olmo_hybrid import (conv_with_tail, decode_attend, history_attend,
                          init_keys, state_rows)  # noqa: F401 (family names)
from ..kv.paged_cache import (HybridKVState, init_kv_state,  # noqa: F401 (family names)
                              kv_logical, kv_page_bytes, lane_padded,
                              write_decode_kv, write_prefill_kv)
from ..ops import ssd
from ..ops.attention import (causal_attention, on_tpu, select_paged_attention,
                             select_prefill_attention)
from ..quantize import embed_rows, qmm, qmm_t

STEP_AUX = True
STEP_KIND = "token"  # a decode step yields one token a row (models/__init__.py)


# ----------------------------------------------------------------- params

def layer_kind(config: GraniteHybridConfig, layer: int) -> str:
    """``mamba`` | ``attention``: the layer's entry of ``layer_types``."""
    return config.layer_types[layer]


def init_layer(config: GraniteHybridConfig, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "mamba") -> dict[str, Any]:
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
    if kind == "attention":
        Q, KV = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        layer.update({
            "wq": _dense(k[3], (D, Q), D, dtype),
            "wk": _dense(k[4], (D, KV), D, dtype),
            "wv": _dense(k[5], (D, KV), D, dtype),
            "wo": _dense(k[6], (Q, D), Q, dtype)})
        return layer
    H, inner, taps = c.mamba_n_heads, c.mamba_inner, c.conv_kernel
    # the published Mamba-2 initialisation: A uniform in (1, 16), dt
    # log-uniform in (0.001, 0.1) through the inverse softplus, D ones, the
    # convolution's bias uniform within 1 / sqrt(taps); so that random weights
    # decay as trained ones do
    A = jax.random.uniform(k[9], (H,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(k[10], (H,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    bound = 1.0 / math.sqrt(taps)
    layer.update({
        "wz": _dense(k[3], (D, inner), D, dtype),
        "wxbc": _dense(k[4], (D, c.conv_dim), D, dtype),
        "wdt": _dense(k[5], (D, H), D, dtype),
        "wo": _dense(k[6], (inner, D), inner, dtype),
        "conv": _dense(k[7], (taps, c.conv_dim), taps, dtype),
        "conv_bias": jax.random.uniform(k[8], (c.conv_dim,), jnp.float32,
                                        -bound, bound),
        "A_log": jnp.log(A),
        "D": ones(H),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": ones(inner)})
    return layer


def init_trunk(config: GraniteHybridConfig, embed_key: jax.Array,
               head_key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    """The embedding, which is also the head (:func:`_logits`), and the
    final norm."""
    del head_key
    return {
        "embed": _dense(embed_key, (config.vocab_size, config.dim),
                        config.dim, dtype),
        "final_norm": jnp.ones((config.dim,), dtype=jnp.float32),
    }


def init_params(config: GraniteHybridConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype,
                                   kind=layer_kind(config, i))
                        for i in range(config.n_layers)]
    return params


def params_logical(config: GraniteHybridConfig) -> dict[str, Any]:
    """The trunk's logical names, so ``quantize_tree`` takes the wide
    projections and the embedding (ONE matrix, quantised a ROW: the scale of
    a token's row when it is gathered is the scale of that token's logit
    when the matrix is the head, ``quantize.qmm_t``); ``W_dt``, the
    convolution and its bias, ``A_log``, ``D``, ``dt_bias`` and the norms
    stay full precision."""
    ffn = {"mixer_norm": "replicated", "ffn_norm": "replicated",
           "w1": "ffn_up", "w3": "ffn_up", "w2": "ffn_down"}
    mixers = {
        "attention": {"wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
                      "wo": "attn_out"},
        "mamba": {"wz": "attn_qkv", "wxbc": "attn_qkv", "wo": "attn_out",
                  **dict.fromkeys(("wdt", "conv", "conv_bias", "A_log", "D",
                                   "dt_bias", "o_norm"), "replicated")}}
    return {"embed": "vocab_in", "final_norm": "replicated",
            "layers": [{**ffn, **mixers[kind]} for kind in config.layer_types]}


def param_count(config: GraniteHybridConfig) -> int:
    c = config
    D, H, inner = c.dim, c.mamba_n_heads, c.mamba_inner
    ffn = 3 * D * c.ffn_hidden + 2 * D
    attention = 2 * D * (c.n_heads + c.n_kv_heads) * c.head_dim
    mamba = (D * (2 * inner + 2 * c.mamba_d_state + H) + inner * D
             + (c.conv_kernel + 1) * c.conv_dim + 3 * H + inner)
    n_attention = len(c.layers_of("full_attention"))
    return (c.vocab_size * D + D + c.n_layers * ffn + n_attention * attention
            + (c.n_layers - n_attention) * mamba)


# ------------------------------------------------ what the engine looks up

@functools.cache
def _attending(config: GraniteHybridConfig) -> GraniteHybridConfig:
    """The configuration the attention kernels, their references and the
    page writers see: heads as wide as they are stored."""
    return dataclasses.replace(config, head_dim=config.kv_head_dim)


def prefill_impl(impl: str, mesh, seq: int, config: GraniteHybridConfig,
                 itemsize: int = 2) -> str:
    return select_prefill_attention(impl, mesh, seq, config.kv_head_dim,
                                    config.n_kv_heads, itemsize)


def delta_impl(mesh, config: GraniteHybridConfig) -> str:
    """``pallas`` on a TPU mesh whose head geometry the kernels take, else
    the ``jax.numpy`` twin (``ops/ssd.py``); ``olmo_hybrid``'s name for the
    recurrence's implementation, which the benchmark's check prints."""
    takes = ssd.takes(config.mamba_n_heads, config.mamba_head_dim,
                      config.mamba_d_state)
    return "pallas" if on_tpu(mesh) and takes else "jnp"


def prefill_unit(mesh, config: GraniteHybridConfig) -> int:
    """The trunk's, and where the kernel runs also whole chunks of it (the
    ``jax.numpy`` twin pads its own)."""
    unit = llama.prefill_unit(mesh, config)
    return (math.lcm(unit, ssd.CHUNK) if delta_impl(mesh, config) == "pallas"
            else unit)


def paged_impl(mesh, config: GraniteHybridConfig, kv: HybridKVState) -> str:
    return select_paged_attention(mesh, config.kv_head_dim, kv.page_size,
                                  config.n_kv_heads, False)


def delta_body(config: GraniteHybridConfig, mesh, seq: int) -> str | None:
    """``ssd_chunk`` has one body, chunkwise on the MXU: every prefill or
    chunk round of this family that runs the kernel counts as one (None where
    the ``jax.numpy`` twin runs, as ``olmo_hybrid.delta_body``)."""
    return "chunkwise" if delta_impl(mesh, config) == "pallas" else None


def expert_path(config: GraniteHybridConfig, mesh, tokens: int,
                dtype=None) -> None:
    """No routed experts: as the dense trunk answers."""
    return None


def refusals(config: GraniteHybridConfig, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings this family cannot serve yet: ``olmo_hybrid``'s, for
    its reasons (the state pool is the same, and the scan kernels have no
    sharding over a model axis either)."""
    return olmo_hybrid.refusals(config, engine_config, mesh, tiers)


# ---------------------------------------------------------------- forward

def _attention_mixer(layer: dict[str, Any], config: GraniteHybridConfig,
                     ordinal: int, a: jax.Array, kv: HybridKVState, attend
                     ) -> tuple[jax.Array, HybridKVState]:
    """The un-rotated GQA mixer of the normed a [B, S, D];
    ``attend(ordinal, q, k, v, kv) -> ([B, S, H, hd], kv)`` is the step's
    write and read of the layer's pages."""
    c = config
    B, S, _ = a.shape
    # softmax at attention_multiplier through kernels that divide by the
    # square root of the head width THEY see
    scale = c.attention_multiplier * math.sqrt(c.kv_head_dim)
    heads = lambda x, n: lane_padded(x.reshape(B, S, n, c.head_dim))
    q = heads(qmm(a, layer["wq"]) * jnp.asarray(scale, a.dtype), c.n_heads)
    k = heads(qmm(a, layer["wk"]), c.n_kv_heads)
    v = heads(qmm(a, layer["wv"]), c.n_kv_heads)
    out, kv = attend(ordinal, q, k, v, kv)              # [B, S, H, stored]
    return qmm(out[..., :c.head_dim].reshape(B, S, -1), layer["wo"]), kv


def scan(x, dt, layer: dict[str, Any], b, c, valid: jax.Array,
         rows: jax.Array, counts: jax.Array, fresh: jax.Array,
         kv: HybridKVState, ordinal: int, impl: str):
    """The recurrence over the rows' stored state (``ops/ssd.py``: the kernel
    or its twin by ``impl``), a padding token made the identity step, plus
    the skip ``D x``. x [B, S, H P] float32; dt [B, S, H] after the softplus;
    b, c [B, S, N]. -> (y [B, S, H P] float32, kv)."""
    dt = jnp.where(valid[..., None], dt, 0.0)           # identity step on padding
    if impl == "pallas":
        y, state = ssd.ssd_pallas(x, dt, layer["A_log"], b, c, kv.state, rows,
                                  counts, fresh, layer=ordinal)
    else:
        y, state = ssd.ssd_reference(x, dt, layer["A_log"], b, c, kv.state,
                                     rows, fresh, layer=ordinal)
    skip = jnp.repeat(layer["D"].astype(jnp.float32), x.shape[-1] // dt.shape[-1])
    return y + skip * x, kv._replace(state=state)


def _mamba_mixer(layer: dict[str, Any], config: GraniteHybridConfig,
                 ordinal: int, a: jax.Array, stream: jax.Array,
                 valid: jax.Array, rows: jax.Array, counts: jax.Array,
                 fresh: jax.Array, kv: HybridKVState, impl: str
                 ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """The Mamba-2 mixer of the normed a [B, S, D] (``stream``: the same in
    float32, which ``dt`` reads) over the rows' stored state; the other
    arguments as ``olmo_hybrid._linear_mixer``'s. -> (mixed, kv with the
    layer's state written, the layer's new convolution tails [rows, taps, C]:
    :func:`_trunk` puts all layers' back at once)."""
    c = config
    inner, N = c.mamba_inner, c.mamba_d_state
    # the helper reads and rewrites ONE layer's tails, cut out of the pool: a
    # scatter a layer into the whole [36, 65, 3, 4352] pool makes the chip's
    # compiler copy the pool around each of them (61 MB, 2.5 ms, twice a
    # layer a step: PERF.md section 6, PR 53)
    one = kv._replace(conv_tail=kv.conv_tail[ordinal:ordinal + 1])
    conv, one = conv_with_tail(qmm(a, layer["wxbc"]), layer["conv"],
                               layer["conv_bias"], c.conv_kernel, 0, rows,
                               counts, fresh, one)
    x, b, cc = jnp.split(conv, [inner, inner + N], axis=-1)
    # a head's decay is exp(-A softplus(.)) with A up to 16: float32 from the
    # float32 stream, as the delta rule's gates (olmo_hybrid._linear_mixer)
    dt = jax.nn.softplus(
        jnp.dot(stream, layer["wdt"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST) + layer["dt_bias"])
    y, kv = scan(x, dt, layer, b, cc, valid, rows, counts, fresh, kv, ordinal,
                 impl)
    z = qmm(a, layer["wz"]).astype(jnp.float32)
    gated = rms_norm(y * jax.nn.silu(z), layer["o_norm"], c.norm_eps)
    return qmm(gated.astype(a.dtype), layer["wo"]), kv, one.conv_tail[0]


def _trunk(params: dict[str, Any], config: GraniteHybridConfig,
           tokens: jax.Array, positions: jax.Array, valid: jax.Array,
           kv: HybridKVState, slot_ids: jax.Array, attend, mesh
           ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """Every layer over a [B, S] block. positions: absolute, -1 for padding
    (a prefix of each row is real); valid [B, S]: tokens whose cache entries
    are kept. ``attend``: :func:`_attention_mixer`'s. -> (final-normed hidden
    [B, S, D], kv, aux)."""
    c, f32 = config, jnp.float32
    h = embed_rows(params["embed"], tokens)
    # the residual stream is float32, and so is the multiplier's product (12
    # is not a power of two: in bfloat16 it would round every entry again)
    act, x = h.dtype, h.astype(f32) * c.embedding_multiplier
    rows, counts, fresh = state_rows(valid, positions, kv, slot_ids)
    impl = delta_impl(mesh, c)
    ordinal = {"full_attention": 0, "linear_attention": 0}
    tails = []
    for idx, layer in enumerate(params["layers"]):
        mixer = c.mixer_kind(idx)
        stream = rms_norm(x, layer["mixer_norm"], c.norm_eps)
        if mixer == "full_attention":
            mixed, kv = _attention_mixer(layer, c, ordinal[mixer],
                                         stream.astype(act), kv, attend)
        else:
            mixed, kv, tail = _mamba_mixer(layer, c, ordinal[mixer],
                                           stream.astype(act), stream, valid,
                                           rows, counts, fresh, kv, impl)
            tails.append(tail)
        ordinal[mixer] += 1
        x = x + c.residual_multiplier * mixed.astype(f32)
        m = rms_norm(x, layer["ffn_norm"], c.norm_eps).astype(act)
        x = x + c.residual_multiplier * _ffn(layer, m, c.hidden_act).astype(f32)
    kv = kv._replace(conv_tail=jnp.stack(tails))
    zero = jnp.zeros((), f32)
    aux = jnp.stack([zero, zero, zero, jnp.sum((counts > 0).astype(f32)),
                     jnp.sum((rows > 0).astype(f32)),
                     jnp.sum(counts).astype(f32)])
    return rms_norm(x, params["final_norm"], c.norm_eps).astype(act), kv, aux


def _logits(params: dict[str, Any], config: GraniteHybridConfig, x: jax.Array,
            last_idx: jax.Array | None = None) -> jax.Array:
    """The tied head over x [B, S, D], or over each row's ``last_idx`` alone,
    divided by ``logits_scaling``."""
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]
    # the embedding transposed, its row scales on the output; float32 out of
    # the product (``lm_logits`` would round the logits to the compute dtype
    # first: half of this family's whole error against its reference)
    return qmm_t(x, params["embed"], jnp.float32) / config.logits_scaling


def prefill(params: dict[str, Any], config: GraniteHybridConfig,
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
    return _logits(params, config, x, last_idx), kv, aux


def prefill_with_history(params: dict[str, Any], config: GraniteHybridConfig,
                         tokens: jax.Array, positions: jax.Array,
                         kv: HybridKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None
                         ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A [B, S] block of prompt tokens at ABSOLUTE positions (-1 = padding)
    after whatever the rows already hold: a chunk round. An attention layer
    attends over the rows' pages (``ctx_pages`` bounds only them); a Mamba
    layer continues from the row's stored state and tail, or from zero where
    the row's first position is 0. Arguments as
    ``models.llama.prefill_with_history``. -> (logits, kv, aux)."""
    valid, safe = positions >= 0, jnp.maximum(positions, 0)

    def attend(ordinal, q, k, v, kv):
        kv = write_prefill_kv(kv, ordinal, k, v, slot_ids, safe, valid)
        return history_attend(_attending(config), ordinal, q, kv, slot_ids,
                              positions, ctx_pages, paged_impl == "pallas",
                              mesh), kv

    x, kv, aux = _trunk(params, config, tokens, positions, valid, kv,
                        slot_ids, attend, mesh)
    return _logits(params, config, x, last_idx), kv, aux


def decode_step(params: dict[str, Any], config: GraniteHybridConfig,
                tokens: jax.Array, positions: jax.Array, kv: HybridKVState,
                slot_ids: jax.Array, seq_lens: jax.Array,
                ctx_pages: int | None = None,
                write_mask: jax.Array | None = None,
                paged_impl: str = "gather", mesh=None
                ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """One token a slot; arguments as ``models.llama.decode_step``. A row that
    ``write_mask`` leaves out (idle, mid-chunk-prefill, frozen) writes the
    trash page and moves no state. -> (logits [B, V], kv, aux)."""
    c = config
    B = tokens.shape[0]
    valid = (jnp.ones((B,), dtype=bool) if write_mask is None else write_mask)

    def attend(ordinal, q, k, v, kv):
        kv = write_decode_kv(kv, ordinal, k[:, 0], v[:, 0], slot_ids,
                             positions, valid=write_mask)
        out = decode_attend(_attending(c), ordinal, q, kv, slot_ids, seq_lens,
                            ctx_pages, paged_impl, mesh)
        return out.reshape(B, 1, c.n_heads, c.kv_head_dim), kv

    # a decode token never starts a sequence: its position is at least 1
    x, kv, aux = _trunk(params, c, tokens[:, None],
                        jnp.where(valid, jnp.maximum(positions, 1), -1)[:, None],
                        valid[:, None], kv, slot_ids, attend, mesh)
    return _logits(params, c, x[:, 0]), kv, aux
