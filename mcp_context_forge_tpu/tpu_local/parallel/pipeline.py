"""Pipeline parallelism: layer stages over a ``pipe`` mesh axis.

SURVEY.md §2.7 PP: stage-sharded pipeline for models beyond one slice —
the mesh abstraction must support it even though a v5e-8 runs TP. Design
(GPipe-style under ``shard_map``):

- layer params are STACKED with a leading stage axis
  ([n_stages, layers_per_stage, ...]) and sharded on ``pipe``, so each
  device physically holds only its stage's weights;
- the batch splits into M microbatches; activations flow stage→stage via
  ``jax.lax.ppermute`` (ICI neighbor hops), M + n_stages - 1 total steps,
  so all stages stay busy once the pipeline fills;
- embedding and the LM head run outside the pipelined middle (they belong
  to the first/last stage conceptually; computing them replicated keeps
  the stage loop uniform — no per-stage control flow under jit).

Composes with TP: use Mesh(devices.reshape(pipe, model), ('pipe','model'))
and the existing NamedSharding rules on the trailing axes.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import LlamaConfig
from ..models.llama import _attention_block, _ffn_block, rms_norm
from ..ops.attention import causal_attention


def stack_layers(params: dict[str, Any], n_stages: int) -> dict[str, Any]:
    """Rearrange the per-layer param list into stage-stacked arrays:
    layers[L][name] -> stacked[name] with shape [n_stages, L/n_stages, ...].
    Returns {embed, final_norm, lm_head, stages:{name: stacked}}."""
    layers = params["layers"]
    n_layers = len(layers)
    if n_layers % n_stages != 0:
        raise ValueError(f"{n_layers} layers not divisible by {n_stages} stages")
    per_stage = n_layers // n_stages
    stacked = {
        name: jnp.stack([
            jnp.stack([layers[s * per_stage + i][name]
                       for i in range(per_stage)])
            for s in range(n_stages)])
        for name in layers[0]
    }
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            # tied models reuse the embedding as the head (transposed at
            # the projection site — stack_layers stays a pure pytree)
            "lm_head": params.get("lm_head", params["embed"]),
            "stages": stacked}


def _layer_forward(layer: dict[str, Any], config: LlamaConfig, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    h = rms_norm(x, layer["attn_norm"], config.norm_eps, config.norm_plus_one)
    q, k, v = _attention_block(layer, config, h, positions)
    attn = causal_attention(q, k, v, impl="reference")
    x = x + attn.reshape(*attn.shape[:2], -1) @ layer["wo"]
    h = rms_norm(x, layer["ffn_norm"], config.norm_eps, config.norm_plus_one)
    return x + _ffn_block(layer, config, h)


def _stage_forward(stage_layers: dict[str, Any], config: LlamaConfig,
                   x: jax.Array, positions: jax.Array) -> jax.Array:
    """Apply this device's layers_per_stage layers (leading axis scanned)."""
    per_stage = stage_layers["wq"].shape[0]

    def body(i, acc):
        layer = {name: arr[i] for name, arr in stage_layers.items()}
        return _layer_forward(layer, config, acc, positions)

    return jax.lax.fori_loop(0, per_stage, body, x)


def _pipeline_body(stage_stacked: dict[str, Any], x_mb: jax.Array,
                   positions: jax.Array, config: LlamaConfig,
                   axis_name: str) -> jax.Array:
    """Per-device body under shard_map.

    stage_stacked: this stage's layers [1, per_stage, ...] (stage axis
    sharded); x_mb: [M, mb, S, D] microbatched embeddings (replicated);
    returns [M, mb, S, D] final-layer activations (valid on the LAST stage;
    psum'd so every device returns them — cheap for test geometries, and
    the final gather is needed anyway for the replicated head).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    my_layers = {name: arr[0] for name, arr in stage_stacked.items()}
    M, mb, S, D = x_mb.shape
    total_steps = M + n_stages - 1
    shift = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def step(t, carry):
        send, outputs = carry
        # activations hop one stage forward; stage 0 ignores what it receives
        recv = jax.lax.ppermute(send, axis_name, shift)
        feed_idx = jnp.clip(t, 0, M - 1)
        first_stage_in = jax.lax.dynamic_index_in_dim(x_mb, feed_idx, axis=0,
                                                      keepdims=False)
        my_in = jnp.where(stage == 0, first_stage_in, recv)
        out = _stage_forward(my_layers, config, my_in, positions)
        # last stage completes microbatch t-(n_stages-1) at step t
        done_idx = t - (n_stages - 1)
        write_idx = jnp.clip(done_idx, 0, M - 1)
        should_write = (stage == n_stages - 1) & (done_idx >= 0)
        current = jax.lax.dynamic_index_in_dim(outputs, write_idx, axis=0,
                                               keepdims=False)
        new_val = jnp.where(should_write, out, current)
        outputs = jax.lax.dynamic_update_index_in_dim(outputs, new_val,
                                                      write_idx, axis=0)
        return out, outputs

    outputs = jnp.zeros_like(x_mb)
    _, outputs = jax.lax.fori_loop(0, total_steps, step,
                                   (jnp.zeros((mb, S, D), x_mb.dtype),
                                    outputs))
    # broadcast the last stage's outputs to every device (head is replicated)
    is_last = (stage == n_stages - 1).astype(x_mb.dtype)
    return jax.lax.psum(outputs * is_last, axis_name)


def build_pp_forward(mesh: Mesh, config: LlamaConfig, n_stages: int,
                     microbatches: int, axis_name: str = "pipe"):
    """Returns (forward, shard_stacked):

    - ``shard_stacked(stacked)`` places stage-stacked params on the mesh
      (stage axis sharded on ``pipe``, rest replicated);
    - ``forward(stacked, tokens, positions) -> logits [B, S, vocab]`` runs
      embed → pipelined layers (M microbatches) → final norm + head.
    B must divide by ``microbatches``.
    """

    stage_spec = P(axis_name)      # leading stage axis
    replicated = P()

    def shard_stacked(stacked: dict[str, Any]) -> dict[str, Any]:
        put = partial(jax.device_put)
        out = {
            "embed": put(stacked["embed"], NamedSharding(mesh, replicated)),
            "final_norm": put(stacked["final_norm"],
                              NamedSharding(mesh, replicated)),
            "lm_head": put(stacked["lm_head"], NamedSharding(mesh, replicated)),
            "stages": {name: put(arr, NamedSharding(mesh, stage_spec))
                       for name, arr in stacked["stages"].items()},
        }
        return out

    layer_names = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm",
                   "w1", "w3", "w2") + (
        ("bq", "bk", "bv") if config.attn_bias else ())
    body = jax.shard_map(
        partial(_pipeline_body, config=config, axis_name=axis_name),
        mesh=mesh,
        in_specs=({name: stage_spec for name in layer_names},
                  replicated, replicated),
        out_specs=replicated, check_vma=False)

    def forward(stacked: dict[str, Any], tokens: jax.Array,
                positions: jax.Array) -> jax.Array:
        B, S = tokens.shape
        if B % microbatches != 0:
            raise ValueError(f"batch {B} not divisible by {microbatches}"
                             " microbatches")
        mb = B // microbatches
        x = stacked["embed"][tokens]                      # [B, S, D]
        if config.embed_multiplier != 1.0:  # Gemma sqrt(dim) scaling
            x = x * jnp.asarray(config.embed_multiplier, dtype=x.dtype)
        x_mb = x.reshape(microbatches, mb, S, -1)
        pos_mb = positions[:mb]                           # identical rows
        out = body(stacked["stages"], x_mb, pos_mb)       # [M, mb, S, D]
        x = out.reshape(B, S, -1)
        x = rms_norm(x, stacked["final_norm"], config.norm_eps, config.norm_plus_one)
        head = (stacked["lm_head"].T if config.tie_embeddings
                else stacked["lm_head"])
        return (x @ head).astype(jnp.float32)

    return jax.jit(forward), shard_stacked
