"""Generation by diffusion over blocks on the GQA trunk with QK-norm and many
small experts: the program's ``SdarConfig`` models (``sdar-30b-a3b-d12``). The
contract is in ``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

import numpy as np

# a program without this model family cannot run the configuration: refused
# here, where the family is looked up, before any device work
from mcp_context_forge_tpu.tpu_local.models.configs import SdarConfig

reference = "sdar_plain"

# config.json key -> models/configs.py SdarConfig field
HF_TO_SDAR = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "moe_intermediate_size": "ffn_hidden", "num_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_seq_len",
    "hidden_act": "hidden_act",
}
# the configuration file's "generation" group -> SdarConfig field
GENERATION = ("block_length", "denoising_steps", "confidence_threshold",
              "mask_token_id")


def model_config(name: str, config: dict[str, Any]) -> SdarConfig:
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("norm_topk_prob", True),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False), ("rope_scaling", None)):
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program computes ({want!r})")
    generation = config["generation"]
    if generation.get("remasking_strategy") != "low_confidence_dynamic":
        raise ValueError(f"{name}: the program's fill rule is "
                         f"low_confidence_dynamic")
    fields = {ours: config[theirs] for theirs, ours in HF_TO_SDAR.items()}
    fields.update({key: generation[key] for key in GENERATION})
    if "moe_block" in config:
        fields["moe_block"] = config["moe_block"]
    return SdarConfig(name=name, **fields)


class EngineLogits:
    """Logits of the last prompt position and of each forced token's OWN
    position (this family's logits are not shifted), THE WAY THE ENGINE RUNS
    SUCH A SEQUENCE: the whole blocks of the prompt through the family's dense
    ``prefill`` at the engine's bucket, or above it in chunks of the bucket
    through ``prefill_with_history`` (what ``_chunk_round`` dispatches), at the
    engine's own context bucket; then every further block (the prompt's
    remainder and the forced tokens, the last block cut short where the
    sequence ends inside it) as ONE pass over its known tokens through the
    block step's own forward (``prefill_with_history`` at ``block_length``
    positions, what ``block_step`` runs a pass) and cache, at the engine's
    decode bucket. No position of the check holds the mask token: passes with
    masked positions are held by the CPU tests. The engine's params, mesh and
    kernel choices, on a scratch pool laid out like the engine's."""

    def __init__(self, engine, check) -> None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        family, cfg, econf = engine._family, engine.model_config, engine.config
        self.engine, self.page = engine, econf.page_size
        self.block = cfg.block_length
        self.chunk = max(econf.prefill_buckets)
        self.table = econf.max_seq_len // self.page
        self.per_slot = -(-check.tokens // self.page)
        paged = family.paged_impl(engine.mesh, cfg, engine.kv)
        self.impl = {
            "prefill": family.prefill_impl(econf.attn_impl, engine.mesh,
                                           self.chunk, cfg),
            "chunk": paged, "block": paged,
            "experts": {f"{tokens} tokens": family.expert_path(
                cfg, engine.mesh, tokens) for tokens in (self.block, self.chunk)}}
        slot = jnp.zeros((1,), jnp.int32)
        self._scratch = jax.jit(
            partial(family.init_kv_state, cfg, 1 + self.per_slot, self.page, 1,
                    self.table, dtype=engine._kv_dtype, quant=econf.kv_quant),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))
        self._dense = jax.jit(
            lambda params, kv, tok, pos, last: family.prefill(
                params, cfg, tok, pos, kv, slot, attn_impl=self.impl["prefill"],
                mesh=engine.mesh, last_idx=last), donate_argnums=(1,))
        self._hist_fns: dict[tuple[int, bool], Any] = {}

        def hist_fn(ctx_pages: int, whole: bool):
            """The history forward at a context bucket: the logits of every
            position (a block's pass) or of ``last`` alone (a chunk)."""
            if (ctx_pages, whole) not in self._hist_fns:
                self._hist_fns[ctx_pages, whole] = jax.jit(
                    lambda params, kv, tok, pos, last: family.prefill_with_history(
                        params, cfg, tok, pos, kv, slot, ctx_pages=ctx_pages,
                        last_idx=None if whole else last, paged_impl=paged,
                        mesh=engine.mesh), donate_argnums=(1,))
            return self._hist_fns[ctx_pages, whole]

        self._hist_fn = hist_fn

    def chunked(self, n: int) -> bool:
        """Whether the engine would carry a prompt of ``n`` tokens across
        dispatches (``engine._assign_bucket``: above its largest bucket)."""
        return n > self.chunk

    def __call__(self, prompt: list[int], forced: list[int]) -> np.ndarray:
        """[1 + len(forced), V] float32."""
        import jax
        import jax.numpy as jnp

        engine, n, Bl = self.engine, len(prompt), self.block
        sequence = list(prompt) + list(forced)
        if len(sequence) > self.per_slot * self.page:
            raise ValueError(f"check prompt of {n} + {len(forced)} tokens exceeds "
                             f"the {self.per_slot} pages the scratch pool holds")

        def run(tokens, start, width):
            tok = np.full((1, width), engine.tokenizer.pad_id, np.int32)
            pos = np.full((1, width), -1, np.int32)
            tok[0, :len(tokens)] = tokens
            pos[0, :len(tokens)] = np.arange(start, start + len(tokens))
            return (jnp.asarray(tok), jnp.asarray(pos),
                    jnp.asarray([max(len(tokens) - 1, 0)], jnp.int32))

        aligned = n - n % Bl
        rows: dict[int, np.ndarray] = {}
        with engine.mesh:
            scratch = self._scratch()
            table = np.zeros((1, self.table), np.int32)
            table[0, :self.per_slot] = 1 + np.arange(self.per_slot)
            scratch = scratch._replace(
                block_tables=jax.device_put(table, scratch.block_tables.sharding))
            for start in range(0, aligned, self.chunk):
                end = min(start + self.chunk, aligned)
                step = (self._hist_fn(engine._hist_ctx_for(end), False)
                        if self.chunked(n) else self._dense)
                logits, scratch = step(engine.params, scratch,
                                       *run(sequence[start:end], start, self.chunk))
                rows[end - 1] = np.asarray(logits, np.float32)[0]
            for start in range(aligned, len(sequence), Bl):
                block = sequence[start:start + Bl]
                logits, scratch = self._hist_fn(
                    engine._ctx_bucket_for(start + Bl), True)(
                        engine.params, scratch, *run(block, start, Bl))
                logits = np.asarray(logits, np.float32)[0]
                rows.update({start + i: logits[i] for i in range(len(block))})
        for leaf in jax.tree.leaves(scratch):
            leaf.delete()
        return np.stack([rows[at] for at in range(n - 1, len(sequence))])


engine_logits = EngineLogits
