"""Decoder whose layers differ in their MIXER and in their FFN (the ``afmoe``
layer stack), functional like ``models/llama.py``, whose parts it calls.

A layer's KIND names both: ``window.dense``, ``window.experts``,
``full.experts`` (``layer_kind``). With ``RMS_x`` an RMSNorm of its own weight:

    a = RMS_in(x)
    q = RMS_q((a W_q) by head)    k = RMS_k((a W_k) by head)    v = a W_v
    window layer:  q, k rotated at the TRUE positions (``apply_rope``); key j
                   visible to query i iff p_j <= p_i and p_i - p_j < W
    full layer:    NO rotation; every key p_j <= p_i
    o = softmax(q k^T / sqrt(hd)) v                            (GQA)
    x = x + RMS_post_attn((o * sigmoid(a W_g)) W_o)            (the output gate)
    m = RMS_pre_mlp(x)
    dense:    f = SwiGLU(m)
    experts:  s = sigmoid(m W_r) float32; ids = top-k of (s + b);
              w = s[ids] / sum s[ids] * route_scale            (``deepseek.route``)
              f = sum_k w_k Expert_ids_k(m) + Shared(m)
    x = x + RMS_post_mlp(f)

and ``x_0 = Emb(t) sqrt(D)``, an untied head over ``RMS_f(x)``.

**What the layers keep** (``kv/paged_cache.py``). A full layer holds every
token of its sequence in K/V pages under the block table, by its ordinal among
the full layers: the GQA trunk's cache, writers, kernels and references. A
window layer keeps a RING of ``ring_tokens / page`` pages a sequence in the
state's two per-sequence pools, by its ordinal among the window layers,
written through ``ring_view`` by the same writers. Its queries read the
``W`` newest keys: the step program works out the first logical page a row's
lowest query still sees, hands the paged kernel (or the gather reference) the
ring pages from there on (``ring_tables``: as many columns as the narrowest
context bucket that holds a ring) and the positions RELATIVE to that page's
first token. Every mask compares positions, and a difference of positions
does not move; so entries older than the window that a touched page still
holds, entries of an earlier lap and of the row's last tenant are dead by
position, and the kernel's static ``window`` skips the pages wholly behind it.

**Dense prefill** runs both kinds through the causal flash kernel or its
reference WITHOUT a window: a bucket is never longer than the window
(``refusals``), so inside one a window layer is a full one but for the
rotation.

Every step function also returns a float32 vector of counts (``STEP_AUX``,
laid out as ``models/olmo_hybrid.py``'s with two more entries): tokens through
expert layers, token-expert pairs (all held here), the tokens' summed
``min(context, W) / context``, the tokens, the live state rows, 0, and the
live rows' summed context and summed ``min(context, W)``.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from . import llama
from .configs import AfmoeConfig
from .deepseek import route
from .llama import (_dense, _ffn, _history_attention, _history_tile,
                    _paged_decode_attention, apply_rope, expert_block,
                    lm_logits, rms_norm, routed_experts)
from ..kv.paged_cache import (HybridKVState, init_kv_state,  # noqa: F401 (family names)
                              kv_logical, kv_page_bytes, ring_tables,
                              ring_view, with_rings, write_decode_kv,
                              write_prefill_kv)
from ..ops.attention import (causal_attention, select_paged_attention,
                             select_prefill_attention)
from ..quantize import embed_rows, qmm

STEP_AUX = True
STEP_KIND = "token"  # a decode step yields one token a row (models/__init__.py)
SCAN_PASS = 2   # a pass of the expert scan over one expert's weights, in
#                 passes of the row-block kernel (``expert_path``)
ROUTER_BIAS_SCALE = 0.1   # the correction bias is drawn N(0, 0.1): a trained
#                           value is not zero, and zeros would leave it untested


# ----------------------------------------------------------------- params

def layer_kind(config: AfmoeConfig, layer: int) -> str:
    """``<mixer>.<ffn>``: window | full, dense | experts."""
    return f"{config.mixer_kind(layer)}.{config.ffn_kind(layer)}"


def init_layer(config: AfmoeConfig, key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16,
               kind: str = "window.experts") -> dict[str, Any]:
    """One layer's random weights. Window and full layers have the same tree
    (the rotation has no weights); ``kind``'s second half picks the FFN."""
    c = config
    D, E, F = c.dim, c.n_experts, c.moe_ffn_hidden
    Q, KV = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    k = jax.random.split(key, 16)
    ones = lambda n: jnp.ones((n,), dtype=jnp.float32)
    layer = {
        "attn_norm": ones(D), "post_attn_norm": ones(D),
        "ffn_norm": ones(D), "post_ffn_norm": ones(D),
        "wq": _dense(k[0], (D, Q), D, dtype),
        "wk": _dense(k[1], (D, KV), D, dtype),
        "wv": _dense(k[2], (D, KV), D, dtype),
        "wg": _dense(k[3], (D, Q), D, dtype),
        "wo": _dense(k[4], (Q, D), Q, dtype),
        "q_norm": ones(c.head_dim), "k_norm": ones(c.head_dim),
    }
    if kind.endswith(".dense"):
        layer.update({
            "w1": _dense(k[5], (D, c.ffn_hidden), D, dtype),
            "w3": _dense(k[6], (D, c.ffn_hidden), D, dtype),
            "w2": _dense(k[7], (c.ffn_hidden, D), c.ffn_hidden, dtype)})
        return layer
    S = c.n_shared_experts * F
    layer.update({
        "router": _dense(k[8], (D, E), D, jnp.float32),
        "router_bias": ROUTER_BIAS_SCALE * jax.random.normal(
            k[9], (E,), dtype=jnp.float32),
        "w1": _dense(k[10], (E, D, F), D, dtype),
        "w3": _dense(k[11], (E, D, F), D, dtype),
        "w2": _dense(k[12], (E, F, D), F, dtype),
        "shared_w1": _dense(k[13], (D, S), D, dtype),
        "shared_w3": _dense(k[14], (D, S), D, dtype),
        "shared_w2": _dense(k[15], (S, D), S, dtype)})
    return layer


def init_trunk(config: AfmoeConfig, embed_key: jax.Array, head_key: jax.Array,
               dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    return {
        "embed": _dense(embed_key, (config.vocab_size, config.dim),
                        config.dim, dtype),
        "final_norm": jnp.ones((config.dim,), dtype=jnp.float32),
        "lm_head": _dense(head_key, (config.dim, config.vocab_size),
                          config.dim, dtype),
    }


def init_keys(config: AfmoeConfig, key: jax.Array) -> jax.Array:
    """[n_layers + 2] keys: one per layer, then the embedding's and the head's."""
    return jax.random.split(key, config.n_layers + 2)


def init_params(config: AfmoeConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> dict[str, Any]:
    keys = init_keys(config, key)
    params = init_trunk(config, keys[-2], keys[-1], dtype)
    params["layers"] = [init_layer(config, keys[i], dtype,
                                   kind=layer_kind(config, i))
                        for i in range(config.n_layers)]
    return params


def params_logical(config: AfmoeConfig) -> dict[str, Any]:
    """The trunk's logical names (so ``quantize_tree`` takes the projections,
    the gate among them, the FFNs and the expert stacks); the norms, the
    router and its bias stay full precision."""
    attention = {"attn_norm": "replicated", "post_attn_norm": "replicated",
                 "ffn_norm": "replicated", "post_ffn_norm": "replicated",
                 "wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
                 "wg": "attn_qkv", "wo": "attn_out",
                 "q_norm": "replicated", "k_norm": "replicated"}
    ffns = {
        "dense": {"w1": "ffn_up", "w3": "ffn_up", "w2": "ffn_down"},
        "experts": {"router": "replicated", "router_bias": "replicated",
                    "w1": "moe_up", "w3": "moe_up", "w2": "moe_down",
                    "shared_w1": "ffn_up", "shared_w3": "ffn_up",
                    "shared_w2": "ffn_down"}}
    return {"embed": "vocab_in", "final_norm": "replicated",
            "lm_head": "vocab_out",
            "layers": [{**attention, **ffns[config.ffn_kind(i)]}
                       for i in range(config.n_layers)]}


def param_count(config: AfmoeConfig) -> int:
    c = config
    D, Q, KV = c.dim, c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    attention = 3 * D * Q + 2 * D * KV + 2 * c.head_dim + 4 * D
    dense = 3 * D * c.ffn_hidden
    experts = ((c.n_experts + c.n_shared_experts) * 3 * D * c.moe_ffn_hidden
               + D * c.n_experts + c.n_experts)
    return (2 * c.vocab_size * D + D + c.n_layers * attention
            + c.n_dense_layers * dense
            + (c.n_layers - c.n_dense_layers) * experts)


# ------------------------------------------------ what the engine looks up

def prefill_impl(impl: str, mesh, seq: int, config: AfmoeConfig,
                 itemsize: int = 2) -> str:
    """The trunk's choice. A dense prefill has no window bound: it is never
    longer than the window (``refusals``)."""
    return select_prefill_attention(impl, mesh, seq, config.head_dim,
                                    config.n_kv_heads, itemsize)


def prefill_unit(mesh, config: AfmoeConfig) -> int:
    return llama.prefill_unit(mesh, config)


def paged_impl(mesh, config: AfmoeConfig, kv: HybridKVState) -> str:
    return select_paged_attention(mesh, config.head_dim, kv.page_size,
                                  config.n_kv_heads, False)


def expert_path(config: AfmoeConfig, mesh, tokens: int,
                dtype: Any = jnp.bfloat16) -> str:
    """Which formulation the routed experts of a step of ``tokens`` tokens
    trace: ``"grouped"`` (ops/grouped_moe.py at the row-block the trunk's
    ``expert_block`` gives the step) or ``"scan"`` (parallel/moe.py). The
    family's OWN rule, handed to ``llama.routed_experts`` and counted by the
    engine; a pure function of the step's shape (T, k, E, the row-block, the
    activations' dtype through it), the configuration's ``moe_impl`` and the
    mesh.

    Where the kernel runs, and a wide step (T·k >= E·moe_block: the chunk
    rounds), are the trunk's (``llama.expert_path``): one device on the
    ``model`` axis, a caller that names its mesh, ``moe_impl`` grouped*.

    A NARROWER step is weighed in WEIGHT PASSES, not in rows as the trunk
    weighs it. Over many small experts rows are not what a decode-width step
    costs: a pass over an expert's weights takes the same time at 1 row and at
    32 (int8 stacks go through the MXU no faster than they leave HBM).
    - The scan cannot avoid E passes, whatever T and whatever the router
      chose, and in each the weight read and the matmuls serialise: 14.1 us an
      iteration over Trinity-Mini's 6.3 MB experts, 1.81 ms a layer at 1 row
      as at 32 (alone on a v5e: PERF.md section 6, PR 48), 896 iterations and
      13.1 of a decode step's 16.3 ms in the cell (ledger, PR 47).
    - The plan makes one pass a LIVE row-block, the next block's tiles fetched
      under this one's matmuls: 7.5 us, the expert's bytes at the HBM's rate,
      and 0.17 ms a layer for the sort, the gathers and the dead grid steps
      (0.77 ms a layer with 32 rows live, 0.47 with 8, 0.22 with one: the
      same run), so a scan pass is counted as ``SCAN_PASS`` = 2 of them. Live
      blocks are never more than the pairs T·k (a block holds a pair) nor
      than E + T·k // b (every expert's spare block and the full ones); an
      expert no live row chose gets none, and idle rows have no pair.
    So a narrow step is grouped when min(T·k, E + T·k // b) <= 2·E. At 32
    rows of 128 x top-8 (b = 16) that is 144 against 256, and what a step
    really makes is far under the bound. At ONE row (the logits check's
    decode) it is 8 passes against the scan's 128. With the row-block
    ``expert_block`` gives today the bound holds for EVERY narrow step (b
    holds an expert's mean share of the pairs, so T·k // b <= E): on one
    device all of this family's steps are grouped, and the inequality is what
    sends a step back to the scan should the row-block stop following the
    share.

    The trunk's rule stays the trunk's: its values at the block family's
    widths are pinned (tests/benchmark), and no function of the shape tells 32
    tokens of 128 x top-8 from 64. PERF.md section 7 says what removes this
    second rule."""
    whole = mesh is not None and mesh.shape.get("model", 1) == 1
    if (not config.moe_impl.startswith("grouped")
            or (config.moe_impl == "grouped_pallas" and not whole)):
        return "scan"
    pairs, experts = tokens * config.moe_top_k, config.n_experts
    if pairs >= experts * config.moe_block:
        return "grouped"
    # under a share (``n_held`` < ``n_experts``: models/solar_open2.py) only
    # the HELD experts are passed over, by the scan and by the plan, and the
    # pairs that land here are the held experts' share of them; the row-block
    # still follows an expert's share of ALL the pairs (``expert_block``
    # reads the published count)
    held = config.n_held
    here = -(-pairs * held // experts)
    passes = min(here, held + here // expert_block(config, tokens, dtype))
    return "grouped" if passes <= SCAN_PASS * held else "scan"


def refusals(config: AfmoeConfig, engine_config, mesh,
             tiers: bool) -> list[str]:
    """Engine settings this family cannot serve yet, each with its reason.
    The engine refuses to build on any of them; nothing falls back."""
    e, W = engine_config, config.sliding_window
    why = []
    if mesh.shape.get("model", 1) > 1:
        why.append("a mesh with more than one device on the model axis: the "
                   "window layers' rings have no sharding over it yet")
    if e.prefix_cache:
        why.append("prefix_cache: a hit skips tokens whose window-layer K/V "
                   "was never kept (needs the rings' contents at the "
                   "prefix's end: snapshots at page boundaries)")
    if tiers:
        why.append("KV tiers / fabric / chain export / migration "
                   "(prefix_tiers, a pool's prefix index or tier store): the "
                   "spill payload carries the full layers' pages, not a "
                   "sequence's rings")
    if e.spec_decode:
        why.append("spec_decode: a rejected draft's ring entry is dead by "
                   "position like a page's, but no test holds a verify step "
                   "over a wrapped ring to it yet")
    if e.sp_impl != "none":
        why.append(f"sp_impl={e.sp_impl!r}: the sequence-parallel attention "
                   "paths have no window bound")
    if e.kv_quant:
        why.append(f"kv_quant={e.kv_quant!r}: the rings and the full layers' "
                   "pages are full precision only (a ring page's scale "
                   "would span laps)")
    widest = max(e.prefill_buckets)
    if widest > W:
        why.append(f"a prefill bucket of {widest} tokens: a dense prefill "
                   f"has no window bound, so no bucket may be longer than "
                   f"the window ({W})")
    if (config.ring_tokens % e.page_size
            or widest + e.page_size > config.ring_slack):
        why.append(f"page_size {e.page_size} and a chunk of {widest} tokens: "
                   f"a ring holds the window and ring_slack "
                   f"{config.ring_slack} tokens in whole pages, and the slack "
                   f"must hold the widest chunk and a page")
    return why


# ---------------------------------------------------------------- forward

def _qkv(layer: dict[str, Any], config: AfmoeConfig, x: jax.Array,
         positions: jax.Array, rotate: bool):
    """QK-normed projections of x [B, S, D], rotated at positions [B, S] in a
    window layer: q [B, S, H, hd], k / v [B, S, KV, hd]."""
    c = config
    B, S, _ = x.shape
    q = qmm(x, layer["wq"]).reshape(B, S, c.n_heads, c.head_dim)
    k = qmm(x, layer["wk"]).reshape(B, S, c.n_kv_heads, c.head_dim)
    v = qmm(x, layer["wv"]).reshape(B, S, c.n_kv_heads, c.head_dim)
    q = rms_norm(q, layer["q_norm"], c.norm_eps)
    k = rms_norm(k, layer["k_norm"], c.norm_eps)
    if rotate:
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
    return q, k, v


def gated_output(layer: dict[str, Any], a: jax.Array,
                 out: jax.Array) -> jax.Array:
    """The output gate (arXiv:2505.06708, elementwise): the attention's heads
    out [B, S, H, hd] times ``sigmoid(a W_g)`` of the layer's normed input a
    [B, S, D], then ``W_o``. (``models/solar_open2.py`` calls it too.)"""
    gate = jax.nn.sigmoid(qmm(a, layer["wg"]))
    return qmm(out.reshape(*out.shape[:2], -1) * gate, layer["wo"])


@partial(jax.jit, static_argnames=("config", "mesh"))
def _expert_ffn(layer: dict[str, Any], config: AfmoeConfig, x: jax.Array,
                valid: jax.Array, mesh) -> jax.Array:
    """Routed experts (the sigmoid router's choices into the trunk's two
    formulations, by the family's rule) + the shared expert. x [B, S, D];
    valid [B, S]: a row or token without it gets no row of the plan.
    Jitted, so a step program traces and lowers it once a shape and not once
    a layer: inlined seven times a decode program, the plan's sort, scatters
    and gathers cost the cell 16 s of every warm build (94.2 against 78.2 s:
    PERF.md section 6, PR 48)."""
    flat = x.reshape(-1, x.shape[-1])
    ids, weights, _ = route(layer, config, flat)
    routed = routed_experts({k: layer[k] for k in ("w1", "w3", "w2")}, config,
                            flat, ids, weights, mesh, valid, rule=expert_path)
    shared = _ffn({"w1": layer["shared_w1"], "w3": layer["shared_w3"],
                   "w2": layer["shared_w2"]}, flat, config.hidden_act)
    return (routed + shared).reshape(x.shape)


def _ring_pages(config: AfmoeConfig, kv: HybridKVState) -> int:
    return config.ring_tokens // kv.page_size


def _reach(config: AfmoeConfig, mixer: str, kv: HybridKVState, ring, pages):
    """What a layer of kind ``mixer`` attends over: ``ring`` / ``pages`` are
    (tables [B, P], query positions or lengths) as its pools see them, the
    ring's relative to the first page a window touches. -> (K pool, V pool,
    tables, positions or lengths, window or None)."""
    if mixer == "window":
        return kv.state, kv.conv_tail, *ring, config.sliding_window
    return kv.k_pages, kv.v_pages, *pages, None


def _gathered(pool: jax.Array, ordinal: int, tables: jax.Array) -> jax.Array:
    """A pool's pages under ``tables`` [B, P] as one context a row: [B, P *
    page, KV, hd] (the jnp reference path; the kernels walk the table)."""
    ctx = pool[ordinal][tables]
    return ctx.reshape(ctx.shape[0], -1, *ctx.shape[3:])


def _window_reach(config: AfmoeConfig, kv: HybridKVState, slot_ids: jax.Array,
                  lowest: jax.Array, ctx_pages: int | None):
    """Where a step's window layers read: ``lowest`` [B] the lowest query
    position a row (0 for a row without one) -> (ring tables [B, P'] from the
    first logical page that position's window touches, that page's first
    token's position [B]). P': the narrowest power of two that holds a ring
    (a width the kernel's KV blocks divide), no wider than the step's context
    bucket; columns past the row's last page alias earlier ones and are dead
    by position."""
    ring = _ring_pages(config, kv)
    width = min(1 << (ring - 1).bit_length(),
                ctx_pages or kv.block_tables.shape[1])
    first = jnp.maximum(lowest - (config.sliding_window - 1), 0) // kv.page_size
    tables = ring_tables(ring, kv.state_rows[slot_ids], first, width)
    return tables, first * kv.page_size


def _trunk(params: dict[str, Any], config: AfmoeConfig, tokens: jax.Array,
           positions: jax.Array, valid: jax.Array, kv: HybridKVState,
           slot_ids: jax.Array, write, attend, mesh
           ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """Every layer over a [B, S] block at ABSOLUTE positions (-1: padding or
    an idle row). ``write(cache, ordinal, k, v) -> cache`` stores the block's
    K/V in a trunk-shaped cache (the state itself for a full layer, the ring
    view for a window layer); ``attend(mixer, ordinal, q, k, v, kv) -> [B, S,
    H, hd]`` is the step's attention, called after the write. ``valid``
    [B, S]: tokens whose entries are kept. -> (final-normed hidden, kv, aux)."""
    c = config
    x = embed_rows(params["embed"], tokens, c.embed_multiplier)
    safe = jnp.maximum(positions, 0)
    rings = ring_view(kv, _ring_pages(c, kv))
    ordinal = {"window": 0, "full": 0}
    for idx, layer in enumerate(params["layers"]):
        mixer = c.mixer_kind(idx)
        a = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k, v = _qkv(layer, c, a, safe, rotate=mixer == "window")
        if mixer == "window":
            rings = write(rings, ordinal[mixer], k, v)
            kv = with_rings(kv, rings)
        else:
            kv = write(kv, ordinal[mixer], k, v)
        out = attend(mixer, ordinal[mixer], q, k, v, kv)
        ordinal[mixer] += 1
        x = x + rms_norm(gated_output(layer, a, out), layer["post_attn_norm"],
                         c.norm_eps)
        m = rms_norm(x, layer["ffn_norm"], c.norm_eps)
        f = (_expert_ffn(layer, c, m, valid, mesh) if "router" in layer
             else _ffn(layer, m, c.hidden_act))
        x = x + rms_norm(f, layer["post_ffn_norm"], c.norm_eps)
    return (rms_norm(x, params["final_norm"], c.norm_eps), kv,
            _counts(c, positions, valid, kv.state_rows[slot_ids]))


def _counts(config: AfmoeConfig, positions: jax.Array, valid: jax.Array,
            rows: jax.Array) -> jax.Array:
    """The step's counts (module docstring) from its positions [B, S]."""
    c, f32 = config, jnp.float32
    W = float(c.sliding_window)
    live = (positions >= 0) & valid
    context = jnp.where(live, positions + 1, 0).astype(f32)      # [B, S]
    tokens = jnp.sum(live.astype(f32))
    share = jnp.sum(jnp.where(live, jnp.minimum(context, W)
                              / jnp.maximum(context, 1.0), 0.0))
    row_context = jnp.max(context, axis=1)                       # [B]
    expert_layers = c.n_layers - c.n_dense_layers
    return jnp.stack([
        tokens * expert_layers, tokens * expert_layers * c.moe_top_k, share,
        tokens, jnp.sum(((rows > 0) & (row_context > 0)).astype(f32)),
        jnp.zeros((), f32), jnp.sum(row_context),
        jnp.sum(jnp.minimum(row_context, W))])


def _logits(params: dict[str, Any], x: jax.Array,
            last_idx: jax.Array | None) -> jax.Array:
    if last_idx is not None:
        x = x[jnp.arange(x.shape[0]), last_idx]
    return lm_logits(params, x)


def prefill(params: dict[str, Any], config: AfmoeConfig, tokens: jax.Array,
            positions: jax.Array, kv: HybridKVState, slot_ids: jax.Array,
            attn_impl: str = "reference", mesh=None,
            last_idx: jax.Array | None = None
            ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A prompt inside one bucket, from position 0; arguments as
    ``models.llama.prefill``. Both kinds of layer attend causally over the
    bucket's own K/V: it is no longer than the window. -> (logits, kv, aux)."""
    if tokens.shape[1] > config.sliding_window:
        raise ValueError(f"a dense prefill of {tokens.shape[1]} tokens is "
                         f"longer than the window {config.sliding_window}")
    valid, safe = positions >= 0, jnp.maximum(positions, 0)

    def write(cache, ordinal, k, v):
        return write_prefill_kv(cache, ordinal, k, v, slot_ids, safe, valid)

    def attend(mixer, ordinal, q, k, v, kv):
        return causal_attention(q, k, v, valid, impl=attn_impl, mesh=mesh)

    x, kv, aux = _trunk(params, config, tokens, positions, valid, kv,
                        slot_ids, write, attend, mesh)
    return _logits(params, x, last_idx), kv, aux


def prefill_with_history(params: dict[str, Any], config: AfmoeConfig,
                         tokens: jax.Array, positions: jax.Array,
                         kv: HybridKVState, slot_ids: jax.Array,
                         ctx_pages: int | None = None,
                         last_idx: jax.Array | None = None,
                         paged_impl: str = "gather", mesh=None
                         ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """A [B, S] run of tokens at ABSOLUTE positions (-1 = padding) after
    whatever the rows already hold: a chunk round. Arguments as
    ``models.llama.prefill_with_history``; ``ctx_pages`` bounds the full
    layers' walk, a window layer walks its ring. -> (logits, kv, aux)."""
    c = config
    B, S = tokens.shape
    G = c.n_heads // c.n_kv_heads
    tile = _history_tile(S, G)
    valid, safe = positions >= 0, jnp.maximum(positions, 0)
    use_pallas = paged_impl == "pallas"
    full_tables = kv.block_tables[slot_ids]
    if ctx_pages is not None:
        full_tables = full_tables[:, :ctx_pages]
    lowest = jnp.min(jnp.where(valid, positions, jnp.iinfo(jnp.int32).max),
                     axis=1)
    ring_tbl, origin = _window_reach(
        c, kv, slot_ids, jnp.where(jnp.any(valid, axis=1), lowest, 0),
        ctx_pages)
    relative = jnp.where(valid, positions - origin[:, None], -1)

    def write(cache, ordinal, k, v):
        return write_prefill_kv(cache, ordinal, k, v, slot_ids, safe, valid)

    def attend(mixer, ordinal, q, k, v, kv):
        *pools, tables, at, window = _reach(
            c, mixer, kv, (ring_tbl, relative), (full_tables, positions))
        if not use_pallas:
            keys, values = (_gathered(pool, ordinal, tables) for pool in pools)
        tiles = []
        for t0 in range(0, S, tile):
            qs, ps = q[:, t0:t0 + tile], at[:, t0:t0 + tile]
            if use_pallas:
                from ..ops.paged_attention import paged_chunk_attention_pallas
                qg = qs.reshape(B, -1, c.n_kv_heads, G, c.head_dim)
                out = paged_chunk_attention_pallas(
                    qg, *pools, tables, ps, layer=ordinal, mesh=mesh,
                    window=window)
                out = out.reshape(B, -1, c.n_heads, c.head_dim)
            else:
                out = _history_attention(
                    qs, keys, values, jnp.maximum(ps, 0),
                    valid[:, t0:t0 + tile], c, window=window)
            tiles.append(out)
        return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)

    x, kv, aux = _trunk(params, c, tokens, positions, valid, kv, slot_ids,
                        write, attend, mesh)
    return _logits(params, x, last_idx), kv, aux


def decode_step(params: dict[str, Any], config: AfmoeConfig,
                tokens: jax.Array, positions: jax.Array, kv: HybridKVState,
                slot_ids: jax.Array, seq_lens: jax.Array,
                ctx_pages: int | None = None,
                write_mask: jax.Array | None = None,
                paged_impl: str = "gather", mesh=None
                ) -> tuple[jax.Array, HybridKVState, jax.Array]:
    """One token a slot; arguments as ``models.llama.decode_step``. A row that
    ``write_mask`` leaves out (idle, mid-chunk-prefill, frozen) writes the
    trash pages. -> (logits [B, V], kv, aux)."""
    c = config
    B = tokens.shape[0]
    G = c.n_heads // c.n_kv_heads
    valid = (seq_lens > 0 if write_mask is None else write_mask)
    full_tables = kv.block_tables[slot_ids]
    if ctx_pages is not None:
        full_tables = full_tables[:, :ctx_pages]
    ring_tbl, origin = _window_reach(c, kv, slot_ids,
                                     jnp.maximum(seq_lens - 1, 0), ctx_pages)
    relative_lens = jnp.maximum(seq_lens - origin, 0)

    def write(cache, ordinal, k, v):
        return write_decode_kv(cache, ordinal, k[:, 0], v[:, 0], slot_ids,
                               positions, valid=write_mask)

    def attend(mixer, ordinal, q, k, v, kv):
        *pools, tables, lens, window = _reach(
            c, mixer, kv, (ring_tbl, relative_lens), (full_tables, seq_lens))
        if paged_impl == "pallas":
            from ..ops.paged_attention import paged_decode_attention_pallas
            qg = q[:, 0].reshape(B, c.n_kv_heads, G, c.head_dim)
            out = paged_decode_attention_pallas(
                qg, *pools, tables, lens, layer=ordinal, mesh=mesh,
                window=window)
            return out.reshape(B, 1, c.n_heads, c.head_dim)
        keys, values = (_gathered(pool, ordinal, tables) for pool in pools)
        return _paged_decode_attention(q[:, 0], keys, values, lens, c,
                                       window=window)

    x, kv, aux = _trunk(params, c, tokens[:, None],
                        jnp.where(valid, positions, -1)[:, None],
                        valid[:, None], kv, slot_ids, write, attend, mesh)
    return lm_logits(params, x[:, 0]), kv, aux
