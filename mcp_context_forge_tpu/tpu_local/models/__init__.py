"""Model zoo: decoder families (chat) + small encoder (embeddings /
moderation classifier), pure-pytree params for pjit.

A decoder family is one module (``llama``: GQA + RoPE + SwiGLU/Mixtral
experts; ``deepseek``: latent attention, shared + routed experts, and by the
model configuration a sparse selector and a multi-token-prediction block; ``olmo_hybrid``: gated delta-rule linear-attention layers between
full-attention layers; ``sdar``: the GQA trunk with QK-norm and many small
experts under a block-causal mask, generating by diffusion over blocks;
``afmoe``: window layers that keep a ring of their newest keys beside full
layers that keep every page, a QK-normed gated attention, and dense or
sigmoid-routed expert FFNs with a shared expert; ``solar_open2``: delta-rule
layers whose decay is a vector a head (Kimi Delta Attention) beside gated
un-rotated GQA layers, every layer routing over a HELD RANGE of many small
experts beside a shared one; ``granite_hybrid``, the seventh: Mamba-2
state-space layers (one group of B and C shared by all heads, a convolution
with bias, a gated norm) beside un-rotated GQA layers by a LIST of mixer
kinds, four multipliers and a tied head) with
the same set of names: ``init_keys``, ``init_layer``,
``init_trunk``, ``params_logical``, ``param_count``, ``prefill``,
``prefill_with_history``, ``decode_step``, the cache's ``init_kv_state`` /
``kv_logical`` / ``kv_page_bytes``, the kernel choices ``prefill_impl`` /
``paged_impl`` / ``expert_path``, ``prefill_unit`` (the tokens a dense
prefill's length is a whole number of), ``refusals`` (engine settings the
family cannot serve yet), and ``STEP_KIND``: what one decode dispatch of the
family is. ``"token"``: ``decode_step`` yields one token a row (a super-step
scans it K times). ``"block"``: the family gives ``block_step`` in its place,
which fills and commits ``config.block_length`` positions a row in one or
more forward passes on the device; its prefill samples nothing (logits at a
position predict that position), so a request's first tokens come from its
first block. The engine finds the module from the model config's CLASS
(:func:`family_of`): nothing else chooses it.

What a family may declare: a KIND a layer (``layer_kind(config, i)``; the
engine compiles one weight-init program a kind) that names the layer's FFN
(``deepseek``: dense | experts), its MIXER (``olmo_hybrid``:
linear_attention | full_attention) or BOTH (``afmoe``: window | full, dense |
experts, as ``window.experts``; ``solar_open2``: ``gqa.experts`` |
``kda.experts``), read from an interval, a tuple of layer indices or, in
``granite_hybrid``, the model configuration's own list of kinds a layer
(``mamba`` | ``attention``: no interval need hold); cache pools that only some layers hold,
and pools of a fixed size a sequence beside the per-token ones
(``kv/paged_cache.py: kv_pools``); with ``STEP_AUX``, a float32 vector of
counts its step programs return beside the tokens (``engine._step_counts``:
``[tokens through expert layers, pairs on held experts, a summed share, the
rows]``, then ``[live state rows, real tokens scanned]`` from a family with a
state a sequence, ``solar_open2`` filling both halves, then a window family's
two key counts); ``delta_body(config, mesh, seq)``, a family with delta-rule
or state-space layers saying which body of their kernel a prefill of ``seq`` positions a
row traces (``chunkwise`` | ``walk`` | None: the engine counts its prefill
dispatches by it);
and ``drafts_on_device(config) -> bool``: WHERE A SPECULATIVE DRAFT COMES FROM.
A family without the name, or one that answers False, gets the engine's
prompt-lookup drafts (``engine._draft_tokens``) and the plain verify step. A
family that answers True drafts itself: its ``prefill`` /
``prefill_with_history`` take ``hidden=True`` and return the last layer's
hidden states beside the logits, and ``draft_step(params, config, hidden,
next_tokens, positions, kv, slot_ids, aux, ...)`` turns them and the tokens
that FOLLOW each position into draft logits for the token after, writing its
own cache entries. The engine then runs it beside every prefill and chunk
round (the first decode dispatch has a draft) and makes every decode dispatch
under ``spec_decode`` a verify step that also drafts
(``engine._decode_and_sample_draft``); the host keeps the draft with the
request. The acceptance rule and the dead-by-position rule for rejected
drafts are the engine's, the same for both answers (docs/adr/008)."""

from importlib import import_module
from types import ModuleType

from .configs import (AfmoeConfig, DeepseekConfig, EncoderConfig,
                      GraniteHybridConfig, LlamaConfig, OlmoHybridConfig,
                      SdarConfig, SolarOpen2Config, ENCODER_CONFIGS,
                      MODEL_CONFIGS)

_FAMILY_MODULES = {LlamaConfig: "llama", DeepseekConfig: "deepseek",
                   OlmoHybridConfig: "olmo_hybrid", SdarConfig: "sdar",
                   AfmoeConfig: "afmoe", SolarOpen2Config: "solar_open2",
                   GraniteHybridConfig: "granite_hybrid"}


def family_of(model_config) -> ModuleType:
    """The family module that serves ``model_config``, by its class."""
    name = _FAMILY_MODULES.get(type(model_config))
    if name is None:
        raise TypeError(f"no model family for {type(model_config).__name__}")
    return import_module(f"{__name__}.{name}")


__all__ = ["LlamaConfig", "DeepseekConfig", "OlmoHybridConfig",
           "SdarConfig", "AfmoeConfig", "SolarOpen2Config",
           "GraniteHybridConfig", "EncoderConfig",
           "MODEL_CONFIGS",
           "ENCODER_CONFIGS", "family_of"]
