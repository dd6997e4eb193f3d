"""Kimi-Delta-Attention layers (a gated delta rule whose decay is a vector a
head) beside gated un-rotated GQA layers, every layer routing over a held
range of many small experts beside a shared one: the program's
``SolarOpen2Config`` models (``solar-open2-d8-ep8``). The contract is in
``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

# a program without this model family cannot run the configuration: refused
# here, where the family is looked up, before any device work
from mcp_context_forge_tpu.tpu_local.models.configs import SolarOpen2Config

# the same serving path as the other family with a state row a sequence: a
# prompt inside the bucket through the dense ``prefill``, a longer one in
# chunk rounds through ``prefill_with_history`` with the recurrent state and
# the convolution tail carried in the state pool, then ``decode_step``
from benchmark.families.olmo_hybrid import EngineLogits as StateRowLogits

reference = "solar_open2_plain"

# config.json key -> models/configs.py SolarOpen2Config field
HF_TO_SOLAR = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "moe_intermediate_size": "moe_ffn_hidden",
    "num_experts_per_tok": "moe_top_k", "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "kda_allow_neg_eigval": "allow_neg_eigval", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
}


def model_config(name: str, config: dict[str, Any]) -> SolarOpen2Config:
    """``n_routed_experts`` in the file counts the experts HELD here
    (``experts_held`` says which); the router keeps the published count."""
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False), ("norm_topk_prob", True),
                      ("first_k_dense_replace", 0), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program computes ({want!r})")
    layers, gqa = config["num_hidden_layers"], list(config["gqa_layers"])
    if gqa != list(range(0, layers, config["gqa_interval"] + 1)):
        raise ValueError(f"{name}: gqa_layers is not a GQA layer then "
                         f"{config['gqa_interval']} KDA layers, over {layers}")
    linear = config["linear_attn_config"]
    if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
        raise ValueError(f"{name}: the program's KDA layers have as many key "
                         f"heads as value heads")
    lo, hi = config.get("experts_held", (0, config["n_routed_experts"]))
    if hi - lo != config["n_routed_experts"]:
        raise ValueError(f"{name}: experts_held {lo}-{hi} is not the "
                         f"{config['n_routed_experts']} experts the file counts")
    routed = config.get("published", {}).get("n_routed_experts",
                                             config["n_routed_experts"])
    fields = {ours: config[theirs] for theirs, ours in HF_TO_SOLAR.items()}
    if "moe_block" in config:
        fields["moe_block"] = config["moe_block"]
    return SolarOpen2Config(
        name=name, n_experts=int(routed), experts_held=(int(lo), int(hi)),
        linear_n_heads=linear["num_heads"], linear_key_dim=linear["head_dim"],
        linear_value_dim=linear["head_dim"], gate_rank=linear["head_dim"],
        conv_kernel=linear["short_conv_kernel_size"], gqa_layers=tuple(gqa),
        **fields)


class EngineLogits(StateRowLogits):
    """``olmo_hybrid``'s, and the expert formulation each step's width takes."""

    def __init__(self, engine, check) -> None:
        super().__init__(engine, check)
        self.impl["experts"] = {
            f"{tokens} tokens": engine._family.expert_path(
                engine.model_config, engine.mesh, tokens)
            for tokens in (1, self.chunk)}


engine_logits = EngineLogits
