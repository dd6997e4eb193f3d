"""Plain reference of the hybrid decoder whose layers are gated delta-rule
linear attention, every fourth one full softmax attention (Olmo-Hybrid's layer
stack), for the comparison that decides ``correct``. Plain ``jax.numpy`` in
float32 under ``default_matmul_precision("highest")``: no cache, no kernel, no
chunks, no batching, nothing imported from ``mcp_context_forge_tpu``; the
engine's own weight tree, int8 leaves ``{"q", "s"}`` dequantised a layer at a
time.

Deliberately NOT the program's formulation:

- the recurrence TOKEN BY TOKEN (``lax.scan`` over ``t``), a head's state as
  its own ``[d_k, d_v]`` matrix: no chunked form, no state pool, no lane-dense
  layout, no stored convolution tail (the convolution sees the whole sequence,
  left-padded with zeros);
- full attention as one ``[T, T]`` score matrix a head.

Both kinds of layer are Olmo-3's reordered-norm block: ``x + RMSNorm(mixer(x))``
then ``x + RMSNorm(MLP(x))``; QK-norm over the whole projection; no rotary
embedding. A layer is ONE jitted program (two kinds x the check's lengths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6


def dequant(w, reduced_axis: int = 0):
    """A plain or ``{"q","s"}`` weight as float32; ``s`` lacks ``reduced_axis``."""
    if isinstance(w, dict):
        return w["q"].astype(F32) * jnp.expand_dims(w["s"].astype(F32), reduced_axis)
    return jnp.asarray(w, F32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(weight, F32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def full_attention(layer, cfg, x):
    """x [T, D] -> [T, D]: QK-normed causal softmax attention, no rotary."""
    T = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rms(x @ dequant(layer["wq"]), layer["q_norm"], cfg.norm_eps)
    k = _rms(x @ dequant(layer["wk"]), layer["k_norm"], cfg.norm_eps)
    v = x @ dequant(layer["wv"])
    q, k, v = q.reshape(T, H, hd), k.reshape(T, KV, hd), v.reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ dequant(layer["wo"])


def gates(layer, cfg, x):
    """(g [T, H] log-decay, beta [T, H]) in float32."""
    a = x @ dequant(layer["wa"])
    b = x @ dequant(layer["wb"])
    g = -jnp.exp(jnp.asarray(layer["A_log"], F32)) * jax.nn.softplus(
        a + jnp.asarray(layer["dt_bias"], F32))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.allow_neg_eigval else 1.0)
    return g, beta


def linear_attention(layer, cfg, x, state_dtype=F32, gate_dtype=F32):
    """x [T, D] -> [T, D]: the gated delta rule, one token after the other.
    ``state_dtype`` / ``gate_dtype`` other than float32 are the tolerance's
    sabotaged readings (``sabotaged``), never the reference."""
    T = x.shape[0]
    H, dk, dv = cfg.linear_n_heads, cfg.linear_key_dim, cfg.linear_value_dim
    raw = jnp.concatenate([x @ dequant(layer["wq"]), x @ dequant(layer["wk"]),
                           x @ dequant(layer["wv"])], axis=-1)        # [T, C]
    taps = cfg.conv_kernel
    padded = jnp.concatenate([jnp.zeros((taps - 1, raw.shape[1]), F32), raw])
    weight = jnp.asarray(layer["conv"], F32)
    conv = jax.nn.silu(sum(weight[i] * padded[i:i + T] for i in range(taps)))
    q, k, v = jnp.split(conv, [H * dk, 2 * H * dk], axis=-1)
    q = _l2(q.reshape(T, H, dk)) * dk ** -0.5
    k = _l2(k.reshape(T, H, dk))
    v = v.reshape(T, H, dv)
    g, beta = gates(layer, cfg, x.astype(gate_dtype).astype(F32)
                    if gate_dtype != F32 else x)
    if gate_dtype != F32:
        g = g.astype(gate_dtype).astype(F32)
        beta = beta.astype(gate_dtype).astype(F32)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S.astype(F32) * jnp.exp(gt)[:, None, None]
        err = vt - jnp.einsum("hkv,hk->hv", S, kt)
        S = S + jnp.einsum("hk,hv->hkv", kt, err * bt[:, None])
        return S.astype(state_dtype), jnp.einsum("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), state_dtype),
                        (q, k, v, g, beta))
    z = (x @ dequant(layer["wg"])).reshape(T, H, dv)
    gated = _rms(o, layer["o_norm"], cfg.norm_eps) * jax.nn.silu(z)
    return gated.reshape(T, H * dv) @ dequant(layer["wo"])


@functools.partial(jax.jit, static_argnames=("cfg", "state_dtype", "gate_dtype"))
def layer_step(layer, x, cfg, state_dtype=F32, gate_dtype=F32):
    """One decoder layer over the whole sequence x [T, D]."""
    if "A_log" in layer:
        mixed = linear_attention(layer, cfg, x, state_dtype, gate_dtype)
    else:
        mixed = full_attention(layer, cfg, x)
    x = x + _rms(mixed, layer["mixer_norm"], cfg.norm_eps)
    mlp = (jax.nn.silu(x @ dequant(layer["w1"])) * (x @ dequant(layer["w3"]))) \
        @ dequant(layer["w2"])
    return x + _rms(mlp, layer["ffn_norm"], cfg.norm_eps)


def _embed(embed, tokens):
    if isinstance(embed, dict):     # per-row scales
        return embed["q"][tokens].astype(F32) * embed["s"][tokens].astype(F32)[:, None]
    return embed[tokens].astype(F32)


HEAD_BLOCKS = 8     # the head a slice of the vocabulary at a time: a float32
#                     copy of a 100352-wide head is 1.5 GB beside the engine


@jax.jit
def _head_block(x, head):
    return x @ dequant(head)


def _head(x, head):
    vocab = (head["q"] if isinstance(head, dict) else head).shape[1]
    if vocab % HEAD_BLOCKS:
        return _head_block(x, head)
    width = vocab // HEAD_BLOCKS
    cut = lambda a, i: jax.lax.slice_in_dim(a, i * width, (i + 1) * width,
                                            axis=a.ndim - 1)
    return jnp.concatenate([_head_block(x, jax.tree.map(lambda a: cut(a, i), head))
                            for i in range(HEAD_BLOCKS)], axis=-1)


def forward(params, model_config, tokens, positions, state_dtype=F32,
            gate_dtype=F32):
    """Logits [len(positions), V] of the full forward pass over ``tokens`` at
    the stated ``positions``, and None (nothing is routed)."""
    cfg = model_config
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        for layer in params["layers"]:
            x = layer_step(layer, x, cfg, state_dtype, gate_dtype)
        x = _rms(x[jnp.asarray(positions)], params["final_norm"], cfg.norm_eps)
        return _head(x, params["lm_head"]), None
