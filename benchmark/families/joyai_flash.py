"""Latent attention + shared and routed experts WITHOUT a selector, with a
multi-token-prediction block that drafts for the engine's verify step: the
program's ``DeepseekConfig`` models that have the block and no selector
(``joyai-llm-flash-d5-ep4``). The contract is in
``benchmark/families/__init__.py``."""

from __future__ import annotations

from typing import Any

import numpy as np

# a program whose latent family has no multi-token-prediction block cannot run
# the configuration: refused here, where the family is looked up, before any
# device work
from mcp_context_forge_tpu.tpu_local.models.deepseek import draft_step  # noqa: F401

reference = "joyai_flash_plain"

# config.json key -> models/configs.py DeepseekConfig field: the selector
# model's map without its selector's keys, with the block's count
from benchmark.families.deepseek_v32 import HF_TO_DEEPSEEK as _WITH_SELECTOR  # noqa: E402

HF_TO_DEEPSEEK = {
    **{theirs: ours for theirs, ours in _WITH_SELECTOR.items()
       if not theirs.startswith("index_")},
    "num_nextn_predict_layers": "n_mtp_blocks"}


def model_config(name: str, config: dict[str, Any]):
    """``n_routed_experts`` in the file counts the experts HELD here
    (``experts_held`` says which); the router keeps the published count. No
    ``index_*`` key: no selector. ``rope_scaling`` null: plain frequencies
    (the program's rule is YaRN above ``rope_original_max``, so that is set to
    the model's own length)."""
    from mcp_context_forge_tpu.tpu_local.models.configs import DeepseekConfig

    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("hidden_act", "silu"),
                      ("rope_scaling", None), ("attention_bias", False),
                      ("tie_word_embeddings", False), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{name}: {key}={config[key]!r} is not what the "
                             f"program computes for this family ({want!r})")
    selector = sorted(k for k in config if k.startswith("index_"))
    if selector:
        raise ValueError(f"{name}: {selector} belong to a model with a selector "
                         f"(benchmark/families/deepseek_v32.py)")
    fields = {ours: config[theirs] for theirs, ours in HF_TO_DEEPSEEK.items()}
    lo, hi = config.get("experts_held", (0, config["n_routed_experts"]))
    if hi - lo != config["n_routed_experts"]:
        raise ValueError(f"{name}: experts_held {lo}-{hi} is not the "
                         f"{config['n_routed_experts']} experts the file counts")
    routed = config.get("published", {}).get("n_routed_experts",
                                             config["n_routed_experts"])
    return DeepseekConfig(name=name, n_routed_experts=int(routed),
                          experts_held=(int(lo), int(hi)),
                          rope_original_max=int(config["max_position_embeddings"]),
                          **fields)


def rejected_draft(token: int) -> int:
    """A draft the check's verify steps carry that is NOT the next token: the
    entries written at its position are wrong until the next step overwrites
    them, as after every rejection in the cell."""
    return 32 + (token - 32 + 1) % 95


class EngineLogits:
    """Per position the main logits BESIDE the block's draft logits for the
    same next token (``[1 + len(forced), 2 V]``, as the reference's
    ``forward``), THE WAY THE ENGINE SERVES SUCH A PROMPT WITH ``spec_decode``
    ON: the prompt in chunks of the prefill bucket through
    ``prefill_with_history`` with the block's pass beside each (``draft_step``
    over the chunk's positions, each with the token that follows it: what
    ``engine._draft_beside`` runs), at the engine's own context bucket; then
    each forced token through a VERIFY-WIDTH step, ``[1, 2]`` = the forced
    token and a draft that is wrong (:func:`rejected_draft`), the model's pass
    and the block's over both positions, at the engine's decode bucket (what
    ``engine._decode_and_sample_draft`` runs, the sampled tokens replaced by
    the forced ones). The first position's rows are taken; the second
    position's entries, in the five layers and in the block's, are the
    rejected-draft entries that the next call overwrites. The engine's params,
    mesh and kernel choice, on a scratch pool laid out like the engine's."""

    def __init__(self, engine, check) -> None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        family, cfg, econf = engine._family, engine.model_config, engine.config
        self.engine, self.page = engine, econf.page_size
        self.chunk = max(econf.prefill_buckets)
        self.table = econf.max_seq_len // self.page
        self.per_slot = -(-(check.tokens + 1) // self.page)
        paged = family.paged_impl(engine.mesh, cfg, engine.kv)
        self.impl = {"chunk": paged, "verify": paged, "moe": cfg.moe_impl}
        slot = jnp.zeros((1,), jnp.int32)
        self._scratch = jax.jit(
            partial(family.init_kv_state, cfg, 1 + self.per_slot, self.page, 1,
                    self.table, dtype=engine._kv_dtype),
            out_shardings=jax.tree.map(lambda a: a.sharding, engine.kv))
        self._fns: dict[tuple[int, int], Any] = {}

        def both(params, kv, tok, nxt, pos, pick, ctx_pages):
            """The model's pass and the block's over one [1, S] block: the
            main logits and the draft logits of rows ``pick`` [n]."""
            logits, kv, aux, hidden = family.prefill_with_history(
                params, cfg, tok, pos, kv, slot, ctx_pages=ctx_pages,
                paged_impl=paged, mesh=engine.mesh, hidden=True)
            drafts, kv, _ = family.draft_step(
                params, cfg, hidden, nxt, pos, kv, slot, aux,
                ctx_pages=ctx_pages, paged_impl=paged, mesh=engine.mesh)
            return logits[0, pick], drafts[0, pick], kv

        def step_fn(width: int, ctx_pages: int):
            if (width, ctx_pages) not in self._fns:
                self._fns[width, ctx_pages] = jax.jit(
                    partial(both, ctx_pages=ctx_pages), donate_argnums=(1,))
            return self._fns[width, ctx_pages]

        self._step_fn = step_fn

    def __call__(self, prompt: list[int], forced: list[int]) -> np.ndarray:
        """[1 + len(forced), 2 V] float32."""
        import jax
        import jax.numpy as jnp

        engine, n = self.engine, len(prompt)
        if n + len(forced) + 1 > self.per_slot * self.page:
            raise ValueError(f"check prompt of {n} + {len(forced)} tokens exceeds "
                             f"the {self.per_slot} pages the scratch pool holds")
        known = list(prompt) + list(forced)
        main: dict[int, np.ndarray] = {}      # position -> logits there
        draft: dict[int, np.ndarray] = {}     # position -> the block's logits there

        def run(start: int, tokens: list[int], follows: list[int], width: int,
                ctx_pages: int, keep: list[int], scratch):
            """One block of ``tokens`` from position ``start``; keeps the rows
            of the absolute positions ``keep``."""
            tok = np.full((1, width), engine.tokenizer.pad_id, np.int32)
            nxt = np.full((1, width), engine.tokenizer.pad_id, np.int32)
            pos = np.full((1, width), -1, np.int32)
            tok[0, :len(tokens)], nxt[0, :len(tokens)] = tokens, follows
            pos[0, :len(tokens)] = np.arange(start, start + len(tokens))
            pick = jnp.asarray([p - start for p in keep], jnp.int32)
            logits, drafts, scratch = self._step_fn(width, ctx_pages)(
                engine.params, scratch, jnp.asarray(tok), jnp.asarray(nxt),
                jnp.asarray(pos), pick)
            for i, p in enumerate(keep):
                main[p] = np.asarray(logits[i], np.float32)
                draft[p] = np.asarray(drafts[i], np.float32)
            return scratch

        with engine.mesh:
            scratch = self._scratch()
            table = np.zeros((1, self.table), np.int32)
            table[0, :self.per_slot] = 1 + np.arange(self.per_slot)
            scratch = scratch._replace(block_tables=jax.device_put(
                table, scratch.block_tables.sharding))
            for start in range(0, n, self.chunk):
                end = min(start + self.chunk, n)
                scratch = run(start, known[start:end], known[start + 1:end + 1],
                              self.chunk, engine._hist_ctx_for(end),
                              [p for p in (n - 2, n - 1) if start <= p < end],
                              scratch)
            for j, token in enumerate(forced):
                # the token that truly follows (the step's own sample in the
                # engine): the block's first row takes it; past the check's
                # last token nothing is known and nothing is kept
                follow = known[n + j + 1] if j + 1 < len(forced) else token
                scratch = run(n + j, [token, rejected_draft(follow)],
                              [follow, follow], 2,
                              engine._ctx_bucket_for(n + j + 2), [n + j], scratch)
        for leaf in jax.tree.leaves(scratch):
            leaf.delete()
        return np.stack([np.concatenate([main[p], draft[p - 1]])
                         for p in range(n - 1, n + len(forced))])


engine_logits = EngineLogits
