"""Plain reference of the hybrid decoder whose layers are Mamba-2 state-space
mixers or un-rotated GQA by a list (IBM Granite 4.0-H's dense layer stack), for
the comparison that decides ``correct``. Plain ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no cache, no kernel, no chunks, no
batching, nothing imported from ``mcp_context_forge_tpu``; the engine's own
weight tree, int8 leaves ``{"q", "s"}`` dequantised a layer at a time.

Deliberately NOT the program's formulation:

- the recurrence TOKEN BY TOKEN (``lax.scan`` over ``t``), a head's state as
  its own ``[N, P]`` matrix: no chunkwise form, no state pool, no lane-dense
  layout, no stored convolution tail (the convolution sees the whole
  sequence, left-padded with zeros);
- attention as one ``[T, T]`` score matrix a head, scaled by
  ``attention_multiplier`` ITSELF (the program scales ``q`` so that kernels
  which divide by ``sqrt(head_dim)`` come out there);
- the head as the embedding's transpose, dequantised a slice of the
  vocabulary at a time.

Departures from the published layer, both sides alike and each under
``assumed`` in the configuration's file: the fused ``in_proj`` is held as its
three column blocks ``z | xBC | dt`` and the fused MLP input as its two
halves (the same products); ``dt`` is the softplus with no ``time_step``
clamp; weights are random from the program's seed.

``forward(..., variant=...)`` computes a named WRONG program instead, for the
controls of the tolerance and of the tests (``VARIANTS``): one of the four
multipliers left out, the norm before the gate, the convolution's bias or the
skip ``D x`` dropped, or a bfloat16 state rounded every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
VARIANTS = (None, "bf16_state", "no_embedding_multiplier",
            "no_residual_multiplier", "no_attention_multiplier",
            "no_logits_scaling", "norm_before_gate", "no_conv_bias", "no_skip")


def dequant(w, reduced_axis: int = 0):
    """A plain or ``{"q","s"}`` weight as float32; ``s`` lacks ``reduced_axis``."""
    if isinstance(w, dict):
        return w["q"].astype(F32) * jnp.expand_dims(w["s"].astype(F32), reduced_axis)
    return jnp.asarray(w, F32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(weight, F32)


def attention(layer, cfg, a, variant):
    """a [T, D] -> [T, D]: causal softmax at ``attention_multiplier``, no
    rotary embedding, no bias, no QK-norm."""
    T = a.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (a @ dequant(layer["wq"])).reshape(T, H, hd)
    k = (a @ dequant(layer["wk"])).reshape(T, KV, hd)
    v = (a @ dequant(layer["wv"])).reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scale = (hd ** -0.5 if variant == "no_attention_multiplier"
             else cfg.attention_multiplier)
    scores = jnp.einsum("thd,shd->hts", q, k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ dequant(layer["wo"])


def mamba(layer, cfg, a, variant):
    """a [T, D] -> [T, D]: the Mamba-2 mixer, one token after the other."""
    T = a.shape[0]
    H, P, N = cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    inner, taps = H * P, cfg.conv_kernel
    z = a @ dequant(layer["wz"])
    raw = a @ dequant(layer["wxbc"])                              # [T, C]
    dt = jax.nn.softplus(a @ dequant(layer["wdt"])
                         + jnp.asarray(layer["dt_bias"], F32))    # [T, H]
    padded = jnp.concatenate([jnp.zeros((taps - 1, raw.shape[1]), F32), raw])
    weight = jnp.asarray(layer["conv"], F32)
    conv = sum(weight[i] * padded[i:i + T] for i in range(taps))
    if variant != "no_conv_bias":
        conv = conv + jnp.asarray(layer["conv_bias"], F32)
    conv = jax.nn.silu(conv)
    x = conv[:, :inner].reshape(T, H, P)
    B, C = conv[:, inner:inner + N], conv[:, inner + N:]
    A = -jnp.exp(jnp.asarray(layer["A_log"], F32))
    state_dtype = jnp.bfloat16 if variant == "bf16_state" else F32

    def step(S, xs):
        xt, dtt, bt, ct = xs
        S = (S.astype(F32) * jnp.exp(dtt * A)[:, None, None]
             + jnp.einsum("n,hp->hnp", bt, dtt[:, None] * xt))
        return S.astype(state_dtype), jnp.einsum("hnp,n->hp", S, ct)

    _, y = jax.lax.scan(step, jnp.zeros((H, N, P), state_dtype), (x, dt, B, C))
    if variant != "no_skip":
        y = y + jnp.asarray(layer["D"], F32)[:, None] * x
    y = y.reshape(T, inner)
    if variant == "norm_before_gate":
        gated = _rms(y, layer["o_norm"], cfg.norm_eps) * jax.nn.silu(z)
    else:
        gated = _rms(y * jax.nn.silu(z), layer["o_norm"], cfg.norm_eps)
    return gated @ dequant(layer["wo"])


@functools.partial(jax.jit, static_argnames=("cfg", "variant"))
def layer_step(layer, x, cfg, variant=None):
    """One decoder layer over the whole sequence x [T, D]."""
    r = 1.0 if variant == "no_residual_multiplier" else cfg.residual_multiplier
    a = _rms(x, layer["mixer_norm"], cfg.norm_eps)
    mixer = mamba if "A_log" in layer else attention
    x = x + r * mixer(layer, cfg, a, variant)
    m = _rms(x, layer["ffn_norm"], cfg.norm_eps)
    mlp = (jax.nn.silu(m @ dequant(layer["w1"])) * (m @ dequant(layer["w3"]))) \
        @ dequant(layer["w2"])
    return x + r * mlp


def _embed(embed, tokens):
    if isinstance(embed, dict):     # per-row scales
        return embed["q"][tokens].astype(F32) * embed["s"][tokens].astype(F32)[:, None]
    return embed[tokens].astype(F32)


HEAD_BLOCKS = 8     # the head a slice of the vocabulary at a time: a float32
#                     copy of a 100352-wide head is 0.8 GB beside the engine


@jax.jit
def _head_block(x, rows):
    """x [T, D] against a slice of the embedding's ROWS (the tied head)."""
    return x @ dequant(rows, 1).T


def _head(x, embed):
    vocab = (embed["q"] if isinstance(embed, dict) else embed).shape[0]
    if vocab % HEAD_BLOCKS:
        return _head_block(x, embed)
    width = vocab // HEAD_BLOCKS
    cut = lambda a, i: jax.lax.slice_in_dim(a, i * width, (i + 1) * width, axis=0)
    return jnp.concatenate([_head_block(x, jax.tree.map(lambda a: cut(a, i), embed))
                            for i in range(HEAD_BLOCKS)], axis=-1)


def forward(params, model_config, tokens, positions, variant: str | None = None):
    """Logits [len(positions), V] of the full forward pass over ``tokens`` at
    the stated ``positions``, and None (nothing is routed)."""
    cfg = model_config
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        if variant != "no_embedding_multiplier":
            x = x * cfg.embedding_multiplier
        for layer in params["layers"]:
            x = layer_step(layer, x, cfg, variant)
        x = _rms(x[jnp.asarray(positions)], params["final_norm"], cfg.norm_eps)
        logits = _head(x, params["embed"])
        if variant != "no_logits_scaling":
            logits = logits / cfg.logits_scaling
        return logits, None
