"""Plain reference of the decoder whose layers are Kimi-Delta-Attention or
gated un-rotated GQA over a routed expert FFN (the ``solar_open2`` layer
stack), for the comparison that decides ``correct`` and for the CPU tests.
Plain ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
the whole sequence at once, no cache, no kernel, no chunks, no batching,
nothing imported from ``mcp_context_forge_tpu``; the engine's own weight tree,
int8 leaves ``{"q", "s"}`` dequantised a layer (an expert) at a time.

**The layer equations** (``config.json`` of Solar-Open2-250B, ``model_type``
``solar_open2``: 48 layers, hidden 4096, ``gqa_layers`` 0, 4, ... 44 so that a
period is GQA, KDA, KDA, KDA; 64 query / 8 kv heads of 128, ``use_rope``
false, ``use_gqa_gate`` true; ``linear_attn_config`` 64 heads of 128, conv 4,
``kda_use_full_proj`` false, ``kda_allow_neg_eigval`` true; 320 routed experts
of width 1280 top-8 beside 1 shared, ``norm_topk_prob``,
``routed_scaling_factor`` 1, ``first_k_dense_replace`` 0; ``rms_norm_eps``
1e-5; vocabulary 196608 untied). ``RMS_x`` is an RMSNorm with its own weight;
pre-norm residual blocks; no rotary embedding anywhere. For tokens ``t_i``:

1. ``x = E[t]``.
2. ``a = RMS_mixer(x)``.
3. A GQA layer: ``q = (a Wq) as [T, H, hd]``, ``k, v = (a Wk), (a Wv) as [T,
   KV, hd]``; ``s_ij = q_i . k_j / sqrt(hd)``, key j visible to query i iff
   ``j <= i``; query head g reads kv head ``g // (H / KV)``; ``x = x +
   ((softmax(s) v) as [T, H hd] * sigmoid(a Wg)) Wo`` (the output gate, one
   value a head dimension: arXiv:2505.06708).
4. A KDA layer (Kimi Linear, arXiv:2510.26692): ``q, k, v = SiLU(conv(a Wq)),
   SiLU(conv(a Wk)), SiLU(conv(a Wv))``, ``conv`` causal and depthwise over
   time, 4 taps, no bias, the sequence left-padded with zeros; a head's ``q =
   L2norm(q) d_k^-0.5``, ``k = L2norm(k)`` (eps 1e-6); ``g = -exp(A_log_h)
   softplus((a Wf1) Wf2 + dt_bias)`` in ``R^{H x d_k}`` (a decay a head AND a
   key channel), ``beta = 2 sigmoid(a Wb)`` a head; with ``S`` in ``R^{d_k x
   d_v}`` a head, from zero: ``S <- Diag(exp g) S``, ``e = v - S^T k``, ``S <-
   S + beta k e^T``, ``o = S^T q``; ``x = x + (RMS_o(o) * sigmoid((a Wg1)
   Wg2)) Wo``, ``RMS_o`` over ``d_v`` with one weight vector all heads share.
5. ``m = RMS_ffn(x)``; ``s = sigmoid(m Wr)`` [E]; the ``top_k`` experts by ``s
   + b`` (``b`` the correction bias: it chooses, it does not weigh); ``w =
   s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``; ``x = x +
   sum_k w_k Expert_k(m) + Shared(m)``, every expert a SwiGLU.
6. After the last layer ``RMS_f``, then the head. ``logits_i`` predict token
   i + 1.

**The share** (the one departure from the published layer, and the
program's): the weight tree holds the expert stacks of ``experts_held = [lo,
hi)`` alone, one chip's share of an expert-parallel layer. The router scores
and chooses among all E; a chosen expert outside the range adds nothing (its
chip would), the shared expert is added whole, and that partial result goes on
to the next layer.

Deliberately NOT the program's formulation: the recurrence TOKEN BY TOKEN
(``lax.scan`` over ``t``), a head's state its own ``[d_k, d_v]`` matrix, no
state pool, no stored convolution tail; attention an explicit ``[T, T]`` mask
in blocks of queries; the expert FFN a loop over the held experts,
dequantising one at a time.

``forward(..., variant=...)`` computes a named WRONG program instead, for the
readings a tolerance is set between and for the tests that must tell them
apart: ``"bf16_state"`` (the state rounded to bfloat16 after every token),
``"scalar_decay"`` (a head's channels all decay by the head's MEAN ``g``: the
gated delta rule without Kimi's channels), ``"no_gate"`` (the GQA layers'
output gate left out), ``"int8_activations"`` (the input of every matmul
rounded to 255 levels a token, exact accumulation: the gentlest precision
below the one stated).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6
QUERY_BLOCK = 512
VARIANTS = (None, "bf16_state", "scalar_decay", "no_gate", "int8_activations")


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


def _rounded(x, on: bool):
    """x with each row rounded to 255 levels of its largest magnitude (int8
    activations, symmetric, a scale a token), or x itself."""
    if not on:
        return x
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale


@functools.partial(jax.jit, static_argnames=("heads", "eps", "gate", "int8"))
def _gqa(x, layer, *, heads, eps, gate, int8):
    """x [T, D] -> x after a gated GQA sublayer."""
    H, KV, hd = heads
    T = x.shape[0]
    a = _rounded(_rms(x, layer["mixer_norm"], eps), int8)
    q = (a @ dequant(layer["wq"])).reshape(T, H, hd)
    k = (a @ dequant(layer["wk"])).reshape(T, KV, hd)
    v = (a @ dequant(layer["wv"])).reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    q, k, v = _rounded(q, int8), _rounded(k, int8), _rounded(v, int8)
    key_at = jnp.arange(T)[None, :]
    blocks = []
    for start in range(0, T, QUERY_BLOCK):
        query_at = jnp.arange(start, min(start + QUERY_BLOCK, T))[:, None]
        scores = jnp.einsum("thd,shd->hts", q[start:start + QUERY_BLOCK], k) \
            / jnp.sqrt(F32(hd))
        probs = jax.nn.softmax(
            jnp.where((key_at <= query_at)[None], scores, -jnp.inf), axis=-1)
        blocks.append(jnp.einsum("hts,shd->thd", _rounded(probs, int8), v))
    out = jnp.concatenate(blocks).reshape(T, H * hd)
    if gate:
        out = out * jax.nn.sigmoid(a @ dequant(layer["wg"]))
    return x + _rounded(out, int8) @ dequant(layer["wo"])


@functools.partial(jax.jit, static_argnames=("heads", "eps", "neg_eigval",
                                             "state_dtype", "scalar", "int8"))
def _kda(x, layer, *, heads, eps, neg_eigval, state_dtype, scalar, int8):
    """x [T, D] -> x after a Kimi-Delta-Attention sublayer, one token after
    the other."""
    H, dk, dv = heads
    T = x.shape[0]
    norm = _rms(x, layer["mixer_norm"], eps)
    a = _rounded(norm, int8)
    raw = jnp.concatenate([a @ dequant(layer["wq"]), a @ dequant(layer["wk"]),
                           a @ dequant(layer["wv"])], axis=-1)        # [T, C]
    weight = jnp.asarray(layer["conv"], F32)
    taps = weight.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, raw.shape[1]), F32), raw])
    conv = jax.nn.silu(sum(weight[i] * padded[i:i + T] for i in range(taps)))
    q, k, v = jnp.split(conv, [H * dk, 2 * H * dk], axis=-1)
    q = _l2(q.reshape(T, H, dk)) * dk ** -0.5
    k = _l2(k.reshape(T, H, dk))
    v = v.reshape(T, H, dv)
    # the decay and beta read the normed stream as it is (float32 in the
    # program too), whatever the activations' precision
    f = (norm @ dequant(layer["wf_down"])) @ dequant(layer["wf_up"])
    g = (-jnp.exp(jnp.asarray(layer["A_log"], F32))[:, None] * jax.nn.softplus(
        f + jnp.asarray(layer["dt_bias"], F32)).reshape(T, H, dk))
    if scalar:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(norm @ dequant(layer["wb"])) \
        * (2.0 if neg_eigval else 1.0)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S.astype(F32) * jnp.exp(gt)[:, :, None]
        err = vt - jnp.einsum("hkv,hk->hv", S, kt)
        S = S + jnp.einsum("hk,hv->hkv", kt, err * bt[:, None])
        return S.astype(state_dtype), jnp.einsum("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), state_dtype),
                        (q, k, v, g, beta))
    z = ((a @ dequant(layer["wg_down"])) @ dequant(layer["wg_up"])
         ).reshape(T, H, dv)
    gated = _rms(o, layer["o_norm"], eps) * jax.nn.sigmoid(z)
    return x + _rounded(gated.reshape(T, H * dv), int8) @ dequant(layer["wo"])


def _swiglu(m, w1, w3, w2, int8):
    return _rounded(jax.nn.silu(m @ w1) * (m @ w3), int8) @ w2


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scale", "lo",
                                             "int8"))
def _experts(x, layer, *, eps, top_k, scale, lo, int8):
    """x [T, D] -> (x after the FFN sublayer: the HELD experts' part of the
    routed sum + the shared expert; each token's routing margin: the
    corrected score by which its last chosen expert beat the first one left
    out, over ALL experts). The stacks hold experts lo .. lo + held - 1."""
    m = _rms(x, layer["ffn_norm"], eps)
    scores = jax.nn.sigmoid(m @ jnp.asarray(layer["router"], F32))    # [T, E]
    ranked, chosen = jax.lax.top_k(scores + jnp.asarray(layer["router_bias"], F32),
                                   top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    kept = jnp.take_along_axis(scores, chosen, axis=1)
    kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20) * scale
    gates = jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], chosen].set(kept)
    m = _rounded(m, int8)
    stack = layer["w1"]["q"] if isinstance(layer["w1"], dict) else layer["w1"]

    def one(e, out):
        pick = lambda w: jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, 0, keepdims=False), w)
        # a sliced expert stack [D, F] / [F, D] has its scale on the out axis
        w1, w3, w2 = (dequant(pick(layer[name])) for name in ("w1", "w3", "w2"))
        return out + jax.lax.dynamic_index_in_dim(gates, lo + e, 1) \
            * _swiglu(m, w1, w3, w2, int8)

    routed = jax.lax.fori_loop(0, stack.shape[0], one, jnp.zeros_like(m))
    shared = _swiglu(m, dequant(layer["shared_w1"]), dequant(layer["shared_w3"]),
                     dequant(layer["shared_w2"]), int8)
    return x + routed + shared, margin


@jax.jit
def _embed(embed, tokens):
    if isinstance(embed, dict):     # per-row scales
        return embed["q"][tokens].astype(F32) * embed["s"][tokens].astype(F32)[:, None]
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, final_norm, head, *, eps, int8):
    return _rounded(_rms(x, final_norm, eps), int8) @ dequant(head)


def forward(params, config, tokens, positions, variant: str | None = None):
    """Logits [len(positions), vocab] of ONE full forward pass over ``tokens``
    (at positions 0..) at the stated ``positions``, and the smallest routing
    margin over the layers at each of them.

    ``config`` needs ``n_heads, n_kv_heads, head_dim, linear_n_heads,
    linear_key_dim, linear_value_dim, allow_neg_eigval, norm_eps, moe_top_k,
    routed_scaling_factor, experts_held``; ``params`` is the engine's tree (a
    KDA layer is one that holds an ``A_log``)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    eps, int8 = float(config.norm_eps), variant == "int8_activations"
    gqa = (config.n_heads, config.n_kv_heads, config.head_dim)
    kda = (config.linear_n_heads, config.linear_key_dim, config.linear_value_dim)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        margins = None
        for layer in params["layers"]:
            if "A_log" in layer:
                x = _kda(x, layer, heads=kda, eps=eps,
                         neg_eigval=bool(config.allow_neg_eigval),
                         state_dtype=(jnp.bfloat16 if variant == "bf16_state"
                                      else F32),
                         scalar=variant == "scalar_decay", int8=int8)
            else:
                x = _gqa(x, layer, heads=gqa, eps=eps,
                         gate=variant != "no_gate", int8=int8)
            x, margin = _experts(x, layer, eps=eps, top_k=int(config.moe_top_k),
                                 scale=float(config.routed_scaling_factor),
                                 lo=int(config.experts_held[0]), int8=int8)
            margins = margin if margins is None else jnp.minimum(margins, margin)
        at = jnp.asarray(positions)
        logits = _head(x[at], params["final_norm"], params["lm_head"], eps=eps,
                       int8=int8)
        return logits, margins[at]
