"""Window layers that keep a ring of their newest keys beside full layers that
keep every page, QK-normed gated attention, dense or sigmoid-routed expert
FFNs with a shared expert: the program's ``AfmoeConfig`` models
(``trinity-mini-d8``). The contract is in ``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

import numpy as np

# a program without this model family cannot run the configuration: refused
# here, where the family is looked up, before any device work
from mcp_context_forge_tpu.tpu_local.models.configs import AfmoeConfig

reference = "afmoe_plain"

# config.json key -> models/configs.py AfmoeConfig field
HF_TO_AFMOE = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "ffn_hidden",
    "moe_intermediate_size": "moe_ffn_hidden", "num_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k", "num_dense_layers": "n_dense_layers",
    "num_shared_experts": "n_shared_experts", "route_scale": "routed_scaling_factor",
    "sliding_window": "sliding_window",
    "global_attn_every_n_layers": "global_attn_every",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len", "hidden_act": "hidden_act",
}


def model_config(name: str, config: dict[str, Any]) -> AfmoeConfig:
    for key, want in (("hidden_act", "silu"), ("score_func", "sigmoid"),
                      ("route_norm", True), ("mup_enabled", True),
                      ("tie_word_embeddings", False), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("num_expert_groups", 1), ("num_limited_groups", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program computes ({want!r})")
    every, layers = config["global_attn_every_n_layers"], config["num_hidden_layers"]
    want = [("full_attention" if i % every == every - 1 else "sliding_attention")
            for i in range(layers)]
    if config["layer_types"] != want:
        raise ValueError(f"{name}: layer_types is not {every - 1} window layers "
                         f"then a full one, {layers // every} times over")
    fields = {ours: config[theirs] for theirs, ours in HF_TO_AFMOE.items()}
    if "moe_block" in config:
        fields["moe_block"] = config["moe_block"]
    return AfmoeConfig(name=name, **fields)


class EngineLogits:
    """Last-position logits of a prompt, then one decode step per forced token,
    THE WAY THE ENGINE SERVES SUCH A PROMPT: a prompt inside the prefill bucket
    through the family's dense ``prefill`` at that bucket (padding behind it),
    a longer one in chunks of the bucket through ``prefill_with_history``
    (what ``_chunk_round`` dispatches: the window layers' rings are written,
    wrapped and read across the dispatches, the last chunk padded), at the
    engine's own context bucket; then ``decode_step`` through the pages and
    the rings at the engine's decode bucket. The engine's params, mesh and
    kernel choices, on a scratch pool laid out like the engine's: one slot,
    its pages and its state row beside the trash row."""

    def __init__(self, engine, check) -> None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        family, cfg, econf = engine._family, engine.model_config, engine.config
        self.engine, self.page = engine, econf.page_size
        self.chunk = max(econf.prefill_buckets)
        self.table = econf.max_seq_len // self.page
        self.per_slot = -(-check.tokens // self.page)
        paged = family.paged_impl(engine.mesh, cfg, engine.kv)
        self.impl = {
            "prefill": family.prefill_impl(econf.attn_impl, engine.mesh,
                                           self.chunk, cfg),
            "chunk": paged, "decode": paged,
            "experts": {f"{tokens} tokens": family.expert_path(
                cfg, engine.mesh, tokens) for tokens in (1, self.chunk)}}
        slot = jnp.zeros((1,), jnp.int32)
        self._scratch = jax.jit(
            partial(family.init_kv_state, cfg, 1 + self.per_slot, self.page, 1,
                    self.table, dtype=engine._kv_dtype),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))
        self._dense = jax.jit(
            lambda params, kv, tok, pos, last: family.prefill(
                params, cfg, tok, pos, kv, slot, attn_impl=self.impl["prefill"],
                mesh=engine.mesh, last_idx=last)[:2], donate_argnums=(1,))
        self._chunk_fns: dict[int, Any] = {}
        self._decode_fns: dict[int, Any] = {}

        def chunk_fn(ctx_pages: int):
            if ctx_pages not in self._chunk_fns:
                self._chunk_fns[ctx_pages] = jax.jit(
                    lambda params, kv, tok, pos, last: family.prefill_with_history(
                        params, cfg, tok, pos, kv, slot, ctx_pages=ctx_pages,
                        last_idx=last, paged_impl=paged, mesh=engine.mesh)[:2],
                    donate_argnums=(1,))
            return self._chunk_fns[ctx_pages]

        def decode_fn(ctx_pages: int):
            if ctx_pages not in self._decode_fns:
                self._decode_fns[ctx_pages] = jax.jit(
                    lambda params, kv, tok, pos: family.decode_step(
                        params, cfg, tok, pos, kv, slot, pos + 1,
                        ctx_pages=ctx_pages, paged_impl=paged,
                        mesh=engine.mesh)[:2], donate_argnums=(1,))
            return self._decode_fns[ctx_pages]

        self._chunk_fn, self._decode_fn = chunk_fn, decode_fn

    def chunked(self, n: int) -> bool:
        """Whether the engine would carry a prompt of ``n`` tokens across
        dispatches (``engine._assign_bucket``: above its largest bucket)."""
        return n > self.chunk

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
            scratch = scratch._replace(
                block_tables=jax.device_put(table, scratch.block_tables.sharding),
                state_rows=jax.device_put(np.ones((1,), np.int32),
                                          scratch.state_rows.sharding))
            logits = None
            for start in range(0, n, self.chunk):
                end = min(start + self.chunk, n)
                tokens = np.full((1, self.chunk), engine.tokenizer.pad_id, np.int32)
                tokens[0, :end - start] = prompt[start:end]
                positions = np.full((1, self.chunk), -1, np.int32)
                positions[0, :end - start] = np.arange(start, end)
                step = (self._chunk_fn(engine._hist_ctx_for(end))
                        if self.chunked(n) else self._dense)
                logits, scratch = step(
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
