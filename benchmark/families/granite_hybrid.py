"""Mamba-2 state-space layers beside un-rotated GQA layers by a list of mixer
kinds, four multipliers and a tied head: the program's ``GraniteHybridConfig``
models (``granite-4.0-h-micro``). The contract is in
``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

# a program without this model family cannot run the configuration: refused
# here, where the family is looked up, before any device work
from mcp_context_forge_tpu.tpu_local.models.configs import GraniteHybridConfig

# the same serving path as the other families with a state row a sequence: a
# prompt inside the bucket through the dense ``prefill``, a longer one in
# chunk rounds through ``prefill_with_history`` with the recurrent state and
# the convolution tail carried in the state pool, then ``decode_step``
from benchmark.families.olmo_hybrid import EngineLogits

reference = "granite_hybrid_plain"
engine_logits = EngineLogits

# config.json key -> models/configs.py GraniteHybridConfig field
HF_TO_GRANITE = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "shared_intermediate_size": "ffn_hidden",
    "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "mamba_d_state", "mamba_d_conv": "conv_kernel",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier",
    "logits_scaling": "logits_scaling", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
}
# what the program computes, and nothing else
COMPUTED = (("hidden_act", "silu"), ("attention_bias", False),
            ("mamba_proj_bias", False), ("mamba_conv_bias", True),
            ("mamba_n_groups", 1), ("position_embedding_type", "nope"),
            ("normalization_function", "rmsnorm"),
            ("tie_word_embeddings", True), ("num_local_experts", 0),
            ("num_experts_per_tok", 0))


def model_config(name: str, config: dict[str, Any]) -> GraniteHybridConfig:
    for key, want in COMPUTED:
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program computes ({want!r})")
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if inner != config.get("mamba_expand", 2) * config["hidden_size"]:
        raise ValueError(f"{name}: mamba_n_heads x mamba_d_head = {inner} is "
                         f"not mamba_expand x hidden_size")
    kinds = tuple(config["layer_types"])
    if "attention" not in kinds or "mamba" not in kinds:
        raise ValueError(f"{name}: layer_types names no attention layer, or "
                         f"no mamba layer")
    fields = {ours: config[theirs] for theirs, ours in HF_TO_GRANITE.items()}
    return GraniteHybridConfig(
        name=name, layer_types=kinds, head_dim=config.get(
            "head_dim", config["hidden_size"] // config["num_attention_heads"]),
        **fields)
