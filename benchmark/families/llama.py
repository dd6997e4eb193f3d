"""The GQA + RoPE + SwiGLU trunk with the Mixtral expert FFN: the program's
``LlamaConfig`` models (``mistral-7b``, ``mixtral-8x7b-d8``). The contract is
in ``benchmark/families/__init__.py``."""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

reference = "decoder"
# The check's prefill length at the default lengths, which the accepted
# configurations' tolerances were validated at: allowed whatever the engine's
# bucket (the CPU rehearsal's engine has one of 64).
VALIDATED_SEQ = 128

# HF config.json key -> models/configs.py LlamaConfig field
HF_TO_LLAMA = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_hidden",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len", "hidden_act": "hidden_act",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts", "num_experts_per_tok": "moe_top_k",
}


def model_config(name: str, config: dict[str, Any]):
    from mcp_context_forge_tpu.tpu_local.models.configs import LlamaConfig

    fields = {ours: config[theirs] for theirs, ours in HF_TO_LLAMA.items()
              if theirs in config}
    model = LlamaConfig(name=name, **fields)
    if config.get("head_dim", model.head_dim) != model.head_dim:
        model = dataclasses.replace(model, head_dim_override=config["head_dim"])
    return model


class EngineLogits:
    """Last-position prefill logits, then one decode step per forced token
    through the paged cache — ``models.llama.prefill`` and ``decode_step``
    with the engine's own params, mesh and attention choice, on a scratch pool
    laid out like the engine's. Two programs, traced once for every prompt.

    The prefill is one dense program over the longest prompt padded to a power
    of two (128 at the default lengths: one page, flash-able). Above its
    largest prefill bucket the engine prefills in chunks through the history
    path instead; this check does not drive that path, so it refuses such
    lengths. The configuration that states them brings the chunked check,
    validated on the chip with its tolerance."""

    def __init__(self, engine, check) -> None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        from mcp_context_forge_tpu.tpu_local.kv import init_kv_state
        from mcp_context_forge_tpu.tpu_local.models.llama import decode_step, prefill
        from mcp_context_forge_tpu.tpu_local.ops.attention import (
            select_paged_attention, select_prefill_attention)

        cfg, econf, mesh = engine.model_config, engine.config, engine.mesh
        self.engine, self.page = engine, econf.page_size
        self.seq = 1 << (max(check.prompt_lengths) - 1).bit_length()
        if self.seq > max(VALIDATED_SEQ, *econf.prefill_buckets):
            raise ValueError(
                f"check prompt of {max(check.prompt_lengths)} tokens is above the "
                f"engine's prefill bucket {max(econf.prefill_buckets)}: the engine "
                f"would chunk it through the history path, which "
                f"benchmark/families/llama.py does not drive yet")
        self.per_slot = (self.seq + check.decode_positions + self.page - 1) // self.page
        self.impl = {
            "prefill": select_prefill_attention(
                econf.attn_impl, mesh, self.seq, cfg.head_dim, cfg.n_kv_heads),
            "decode": select_paged_attention(
                mesh, cfg.head_dim, self.page, cfg.n_kv_heads, bool(econf.kv_quant))}
        slot = jnp.zeros((1,), jnp.int32)
        self._scratch = jax.jit(
            partial(init_kv_state, cfg, 1 + self.per_slot, self.page, 1,
                    self.per_slot, dtype=engine._kv_dtype, quant=econf.kv_quant),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))
        self._prefill = jax.jit(lambda params, kv, tok, pos, last: prefill(
            params, cfg, tok, pos, kv, slot, attn_impl=self.impl["prefill"],
            mesh=mesh, last_idx=last))
        self._decode = jax.jit(lambda params, kv, tok, pos: decode_step(
            params, cfg, tok, pos, kv, slot, pos + 1, ctx_pages=self.per_slot,
            paged_impl=self.impl["decode"], mesh=mesh))

    def __call__(self, prompt: list[int], forced: list[int]) -> np.ndarray:
        """[1 + len(forced), V] float32."""
        import jax
        import jax.numpy as jnp

        engine, n = self.engine, len(prompt)
        if n > self.seq or n + len(forced) > self.per_slot * self.page:
            raise ValueError(f"check prompt of {n} + {len(forced)} tokens exceeds "
                             f"the {self.seq} + {self.per_slot} pages traced for")
        tokens = np.full((1, self.seq), engine.tokenizer.pad_id, np.int32)
        tokens[0, :n] = prompt
        positions = np.full((1, self.seq), -1, np.int32)
        positions[0, :n] = np.arange(n)
        with engine.mesh:
            scratch = self._scratch()
            scratch = scratch._replace(block_tables=jax.device_put(
                1 + np.arange(self.per_slot, dtype=np.int32)[None, :],
                scratch.block_tables.sharding))
            logits, scratch = self._prefill(
                engine.params, scratch, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray([n - 1], jnp.int32))
            rows = [np.asarray(logits, np.float32)[0]]
            for j, token in enumerate(forced):
                logits, scratch = self._decode(
                    engine.params, scratch, jnp.asarray([token], jnp.int32),
                    jnp.asarray([n + j], jnp.int32))
                rows.append(np.asarray(logits, np.float32)[0])
        for leaf in jax.tree.leaves(scratch):
            leaf.delete()
        return np.stack(rows)


engine_logits = EngineLogits
