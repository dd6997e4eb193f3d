"""Latent attention + learned sparse selector + shared and routed experts: the
program's ``DeepseekConfig`` models (``deepseek-v3.2-d5-ep16``). The contract
is in ``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

import numpy as np

reference = "deepseek_v32_plain"

# config.json key -> models/configs.py DeepseekConfig field
HF_TO_DEEPSEEK = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "index_n_heads": "index_n_heads",
    "index_head_dim": "index_head_dim", "index_topk": "index_topk",
    "intermediate_size": "ffn_hidden", "moe_intermediate_size": "moe_ffn_hidden",
    "first_k_dense_replace": "n_dense_layers", "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k", "n_group": "n_group",
    "topk_group": "topk_group", "routed_scaling_factor": "routed_scaling_factor",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
}
ROPE_TO_DEEPSEEK = {
    "factor": "rope_factor", "original_max_position_embeddings": "rope_original_max",
    "beta_fast": "rope_beta_fast", "beta_slow": "rope_beta_slow",
    "mscale_all_dim": "rope_mscale_all_dim",
}


def model_config(name: str, config: dict[str, Any]):
    """``n_routed_experts`` in the file counts the experts HELD here
    (``experts_held`` says which); the router keeps the published count."""
    from mcp_context_forge_tpu.tpu_local.models.configs import DeepseekConfig

    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program's router computes ({want!r})")
    fields = {ours: config[theirs] for theirs, ours in HF_TO_DEEPSEEK.items()}
    fields.update({ours: config["rope_scaling"][theirs]
                   for theirs, ours in ROPE_TO_DEEPSEEK.items()})
    lo, hi = config.get("experts_held", (0, config["n_routed_experts"]))
    if hi - lo != config["n_routed_experts"]:
        raise ValueError(f"{name}: experts_held {lo}-{hi} is not the "
                         f"{config['n_routed_experts']} experts the file counts")
    routed = config.get("published", {}).get("n_routed_experts",
                                             config["n_routed_experts"])
    return DeepseekConfig(name=name, n_routed_experts=int(routed),
                          experts_held=(int(lo), int(hi)), **fields)


class EngineLogits:
    """Last-position logits of a prompt, then one decode step per forced token,
    THE WAY THE ENGINE SERVES SUCH A PROMPT: in chunks of its prefill bucket
    through the family's ``prefill_with_history`` (what ``_chunk_round``
    dispatches: chunk i attends to chunks 0..i-1 in the row's pages through the
    selector), at the engine's own context bucket for that length, then
    ``decode_step`` through the latent cache at the engine's decode bucket;
    the engine's params, mesh and kernel choice, on a scratch pool laid out
    like the engine's. One chunk program and one decode program per bucket."""

    def __init__(self, engine, check) -> None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        family, cfg, econf = engine._family, engine.model_config, engine.config
        self.engine, self.page = engine, econf.page_size
        self.chunk = max(econf.prefill_buckets)
        self.table = econf.max_seq_len // self.page
        self.per_slot = -(-check.tokens // self.page)
        self.impl = {"chunk": family.paged_impl(engine.mesh, cfg, engine.kv),
                     "decode": family.paged_impl(engine.mesh, cfg, engine.kv),
                     "moe": cfg.moe_impl}
        slot = jnp.zeros((1,), jnp.int32)
        self._scratch = jax.jit(
            partial(family.init_kv_state, cfg, 1 + self.per_slot, self.page, 1,
                    self.table, dtype=engine._kv_dtype),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))
        self._chunk_fns: dict[int, Any] = {}
        self._decode_fns: dict[int, Any] = {}

        def chunk_fn(ctx_pages: int):
            if ctx_pages not in self._chunk_fns:
                self._chunk_fns[ctx_pages] = jax.jit(
                    lambda params, kv, tok, pos, last: family.prefill_with_history(
                        params, cfg, tok, pos, kv, slot, ctx_pages=ctx_pages,
                        last_idx=last, paged_impl=self.impl["chunk"],
                        mesh=engine.mesh)[:2], donate_argnums=(1,))
            return self._chunk_fns[ctx_pages]

        def decode_fn(ctx_pages: int):
            if ctx_pages not in self._decode_fns:
                self._decode_fns[ctx_pages] = jax.jit(
                    lambda params, kv, tok, pos: family.decode_step(
                        params, cfg, tok, pos, kv, slot, pos + 1,
                        ctx_pages=ctx_pages, paged_impl=self.impl["decode"],
                        mesh=engine.mesh)[:2], donate_argnums=(1,))
            return self._decode_fns[ctx_pages]

        self._chunk_fn, self._decode_fn = chunk_fn, decode_fn

    def __call__(self, prompt: list[int], forced: list[int]) -> np.ndarray:
        """[1 + len(forced), V] float32."""
        import jax
        import jax.numpy as jnp

        engine, n = self.engine, len(prompt)
        if n + len(forced) > self.per_slot * self.page:
            raise ValueError(f"check prompt of {n} + {len(forced)} tokens exceeds "
                             f"the {self.per_slot} pages the scratch pool holds")
        with engine.mesh:
            scratch = self._scratch()
            table = np.zeros((1, self.table), np.int32)
            table[0, :self.per_slot] = 1 + np.arange(self.per_slot)
            scratch = scratch._replace(block_tables=jax.device_put(
                table, scratch.block_tables.sharding))
            logits = None
            for start in range(0, n, self.chunk):
                end = min(start + self.chunk, n)
                tokens = np.full((1, self.chunk), engine.tokenizer.pad_id, np.int32)
                tokens[0, :end - start] = prompt[start:end]
                positions = np.full((1, self.chunk), -1, np.int32)
                positions[0, :end - start] = np.arange(start, end)
                logits, scratch = self._chunk_fn(engine._hist_ctx_for(end))(
                    engine.params, scratch, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray([end - start - 1], jnp.int32))
            rows = [np.asarray(logits, np.float32)[0]]
            for j, token in enumerate(forced):
                logits, scratch = self._decode_fn(engine._ctx_bucket_for(n + j + 1))(
                    engine.params, scratch, jnp.asarray([token], jnp.int32),
                    jnp.asarray([n + j], jnp.int32))
                rows.append(np.asarray(logits, np.float32)[0])
        for leaf in jax.tree.leaves(scratch):
            leaf.delete()
        return np.stack(rows)


engine_logits = EngineLogits
