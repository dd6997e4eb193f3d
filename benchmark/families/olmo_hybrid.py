"""Gated delta-rule linear-attention layers between full-attention layers: the
program's ``OlmoHybridConfig`` models (``olmo-hybrid-7b``). The contract is in
``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

import numpy as np

# a program without this model family cannot run the configuration: refused
# here, where the family is looked up, before any device work
from mcp_context_forge_tpu.tpu_local.models.configs import OlmoHybridConfig

reference = "olmo_hybrid_plain"

# config.json key -> models/configs.py OlmoHybridConfig field
HF_TO_HYBRID = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "ffn_hidden",
    "linear_key_head_dim": "linear_key_dim",
    "linear_value_head_dim": "linear_value_dim",
    "linear_conv_kernel_dim": "conv_kernel",
    "linear_allow_neg_eigval": "allow_neg_eigval",
    "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_seq_len",
}


def model_config(name: str, config: dict[str, Any]) -> OlmoHybridConfig:
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program computes ({want!r})")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError(f"{name}: the program's linear layers have as many "
                         f"key heads as value heads")
    if config.get("rope_parameters", {}).get("rope_theta") is not None:
        raise ValueError(f"{name}: the program's full-attention layers apply "
                         f"no rotary embedding (rope_theta must be null)")
    kinds = config["layer_types"]
    full = [i for i, kind in enumerate(kinds) if kind == "full_attention"]
    if not full:
        raise ValueError(f"{name}: layer_types has no full_attention layer")
    interval = full[0] + 1
    fields = {ours: config[theirs] for theirs, ours in HF_TO_HYBRID.items()}
    model = OlmoHybridConfig(
        name=name, head_dim=config.get(
            "head_dim", config["hidden_size"] // config["num_attention_heads"]),
        linear_n_heads=config["linear_num_key_heads"],
        full_attention_interval=interval, **fields)
    if len(kinds) != model.n_layers or any(
            model.mixer_kind(i) != kind for i, kind in enumerate(kinds)):
        raise ValueError(f"{name}: layer_types is not a full-attention layer "
                         f"every {interval} layers")
    return model


class EngineLogits:
    """Last-position logits of a prompt, then one decode step per forced token,
    THE WAY THE ENGINE SERVES SUCH A PROMPT: a prompt inside the prefill bucket
    through the family's dense ``prefill`` at that bucket (padding behind it),
    a longer one in chunks of the bucket through ``prefill_with_history``
    (what ``_chunk_round`` dispatches: the recurrent state and the convolution
    tail are carried across the dispatches in the state pool, the last chunk
    padded), at the engine's own context bucket; then ``decode_step`` through
    the pages and the state row at the engine's decode bucket. The engine's
    params, mesh and kernel choices, on a scratch pool laid out like the
    engine's: one slot, its pages and its state row beside the trash row."""

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
            "delta": family.delta_impl(engine.mesh, cfg)}
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
