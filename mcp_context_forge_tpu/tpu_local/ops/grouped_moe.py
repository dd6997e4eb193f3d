"""Dropless grouped-GEMM MoE FFN: each token through the experts it chose.

The expert scan (`parallel/moe.py: expert_scan`) runs EVERY expert over
EVERY token and masks — E/k x the needed FFN FLOPs (4 x for Mixtral
8 x top-2, 16 x for 128 x top-8). That is free where an expert's weight
read is far longer than its matmul over the step's rows (a Mixtral decode
step: 176 MB an expert, at most 32 rows), and the whole cost where a step is
bound by the MXU (prefill). Between the two lies a decode-width step over
MANY SMALL experts (a block step of 128 tokens over 128 int8 experts of
4.7 MB): an iteration's matmul takes as long as its weight read, the scan
serialises the two, and the row-block kernel below at a block of 16 rows
overlaps them (one pipelined kernel a layer: 6.6 us a live expert against
the scan's 16 on a v5e). This module computes the same per-token function at
~k/E of the FLOPs with STATIC shapes (XLA requirement), by the block-sparse
trick of MegaBlocks-style grouped GEMMs:

1. flatten the T x k (token, expert) choices and sort them by expert —
   each expert's tokens become contiguous. A pair whose id lies outside
   ``[0, E)`` gets no row: a pair an expert-parallel peer computes (the
   latent family's held range), or a pair of one of the bucket's PADDING
   tokens (`moe_ffn_grouped`'s ``valid``), so the rows follow the live
   tokens and not the bucket;
2. pad every expert group up to a multiple of the row-block Bt. Total
   padded rows are bounded by ``N + E·Bt`` (each group wastes < one
   block), so the buffer and the block count NB = ceil(N/Bt) + E are
   STATIC — dropless without dynamic shapes, no capacity factor, no skew
   cliff — while ``live_blocks``, how many of them hold a row, is a value
   computed on the device;
3. every row-block belongs to exactly ONE expert (``block_expert[NB]``).
   The FFN is then NB independent [Bt, D] x expert GEMMs:
   - XLA path: gather the block's expert weights and einsum — correct
     everywhere, but materializes gathered weights in HBM;
   - Pallas path (TPU): ``block_expert`` and ``live_blocks`` ride scalar
     prefetch, and the BlockSpec index maps DMA exactly the ONE expert's
     weight tiles a block needs from HBM into VMEM — the gather never
     materializes. F is tiled; the [Bt, D] output accumulates in VMEM
     scratch in float32. One kernel body serves full-precision and int8
     ({"q", "s"}) stacks; a block at or past ``live_blocks`` computes
     nothing, fetches nothing and writes zeros;
4. the way back: each token gathers its k rows by the inverse of the sort
   and sums them gate-weighted (all experts held here: nearly every pair
   has a row), or the weighted rows scatter-add to their tokens (a held
   range: most pairs are elsewhere).

Who takes which path, and at which row-block, is the model family's to say
from what it can see (`models/llama.py: expert_path` / `expert_block`,
`models/deepseek.py: expert_path`): the step's rows ``T·k + E·Bt`` against
the scan's ``E·T``, the mesh, the activations' dtype; the window / full
family (`models/afmoe.py: expert_path`) counts passes over an expert's
weights in place of rows. ``Bt`` follows the
step: ``moe_block`` for a wide one, the power of two that holds an expert's
share of the pairs for a narrow one, down to the activations' sublane tile.

Per-token outputs are EXACTLY the scan's (same router math via
``router_probs``, same renormalized gates), whatever rows share a block or
a batch, so the continuous-batching invariant (prefill + decode ≡ one long
prefill) holds — tested against the scan in
tests/tpu_local/test_grouped_moe.py.

FLOPs accounting: the scan runs E·T rows through the FFN; grouped runs at
most NB·Bt = T·k + E·Bt rows (+ router), and the live blocks only. For
Mixtral-shape 8 x top-2 with T=2048, Bt=128: (2048·2 + 8·128) / (8·2048) =
31.3% vs 25% ideal — the E·Bt padding term vanishes as T grows. A block
step of 128 tokens over 128 x top-8 at Bt=16: (1024 + 2048) / 16384 = 18.8%
vs 6.25% ideal, and the padded blocks past the live ones are skipped.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------- routing

def top_k_gates(probs: jax.Array, top_k: int) -> tuple[jax.Array, jax.Array]:
    """The top ``top_k`` experts of each row of router probabilities [T, E]
    and their gates renormalized to sum to one: (ids [T, k], gates [T, k])."""
    _, top_idx = jax.lax.top_k(probs, top_k)                   # [T, k]
    gates = jnp.take_along_axis(probs, top_idx, axis=1)        # [T, k]
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)          # renorm
    return top_idx, gates


def plan_sorted_blocks(expert_ids: jax.Array, gates: jax.Array,
                       n_experts: int, block: int) -> dict[str, jax.Array]:
    """Static-shape block-sparse plan from routing CHOICES: ids and weights,
    however the router made them (softmax top-k, sigmoid group-limited, ...).

    ``expert_ids`` [T, k] index the ``n_experts`` expert stacks held HERE; an
    id outside ``[0, n_experts)`` is a pair routed to an expert some other
    holder computes (expert parallelism's share) and gets no row. ``gates``
    [T, k] are the pairs' final weights.

    Returns:
      sorted_token  [NP]  flat-token index feeding each padded row
      row_valid     [NP]  1.0 for live rows, 0.0 for group padding
      gates         [NP]  weight of the (token, slot) pair
      block_expert  [NB]  owning expert of each row-block
      live_blocks   [1]   blocks that hold a live row (the rest is padding
                          past every group: nothing to compute or fetch)
      pair_row      [T, k] padded row of each pair, NP for a pair with none:
                          the inverse of ``sorted_token``, for the way back
      pair_gates    [T, k] ``gates`` as given, float32
      (NP = NB·block; NB = ceil(T·k/block) + E — both static: every pair
      may land here)
    """
    T, top_k = expert_ids.shape
    E = n_experts
    N = T * top_k
    NB = -(-N // block) + E
    NP = NB * block

    expert_flat = expert_ids.reshape(N).astype(jnp.int32)      # [N]
    here = (expert_flat >= 0) & (expert_flat < E)
    expert_flat = jnp.where(here, expert_flat, E)              # E: elsewhere
    token_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    gate_flat = gates.reshape(N)

    order = jnp.argsort(expert_flat, stable=True)              # [N]
    sorted_expert = expert_flat[order]
    counts = jnp.bincount(sorted_expert, length=E + 1)[:E]     # [E]
    group_start = jnp.cumsum(counts) - counts                  # [E]
    padded_counts = -(-counts // block) * block
    padded_start = jnp.cumsum(padded_counts) - padded_counts   # [E]
    # padded destination of sorted row j: its rank within the group,
    # offset by the group's padded start; pairs held elsewhere sort last
    # and are dropped (destination past the buffer)
    j = jnp.arange(N)
    local = jnp.minimum(sorted_expert, E - 1)
    rank = j - group_start[local]
    dest = jnp.where(sorted_expert < E, padded_start[local] + rank, NP)

    sorted_token = jnp.zeros((NP,), jnp.int32).at[dest].set(
        token_flat[order], mode="drop")
    row_valid = jnp.zeros((NP,), jnp.float32).at[dest].set(1.0, mode="drop")
    gates_padded = jnp.zeros((NP,), jnp.float32).at[dest].set(
        gate_flat[order].astype(jnp.float32), mode="drop")

    # owning expert per block: block b starts at row b·block; an expert
    # owns it iff padded_start[e] <= b·block < padded_start[e]+padded.
    # searchsorted over the padded-end cumsum gives that e; blocks past
    # every group (pure padding) clamp to E-1 and are all-invalid rows.
    padded_end = jnp.cumsum(padded_counts)                     # [E]
    block_starts = jnp.arange(NB) * block
    block_expert = jnp.clip(
        jnp.searchsorted(padded_end, block_starts, side="right"),
        0, E - 1).astype(jnp.int32)
    live_blocks = (padded_end[-1:] // block).astype(jnp.int32)
    pair_row = jnp.zeros((N,), jnp.int32).at[order].set(
        dest.astype(jnp.int32)).reshape(T, top_k)
    return {"sorted_token": sorted_token, "row_valid": row_valid,
            "gates": gates_padded, "block_expert": block_expert,
            "live_blocks": live_blocks, "pair_row": pair_row,
            "pair_gates": gates.astype(jnp.float32)}


def _act(h: jax.Array, act: str) -> jax.Array:
    return (jax.nn.gelu(h, approximate=True) if act == "gelu"
            else jax.nn.silu(h))


# --------------------------------------------------------------- XLA path

def _expert_blocks_xla(x_pad: jax.Array, w1, w3, w2,
                       block_expert: jax.Array, act: str) -> jax.Array:
    """[NB, Bt, D] rows through their owning expert's FFN — pure XLA.
    Gathered per-block weights materialize ([NB, D, F] etc.); fine at
    moderate sizes and the reference semantics for the Pallas kernel.
    Quantized expert stacks ({"q","s"} leaves) dequantize per block —
    XLA fuses the scale multiply into the GEMM epilogue."""
    def take(w):
        if isinstance(w, dict):
            # int8 stacks: q [E, A, B] with the CONTRACTION axis (1)
            # reduced, s [E, B] on the surviving out-channels
            return (w["q"][block_expert].astype(jnp.float32)
                    * w["s"][block_expert][:, None, :]).astype(x_pad.dtype)
        return w[block_expert]

    h = _act(jnp.einsum("btd,bdf->btf", x_pad, take(w1)), act)
    h = h * jnp.einsum("btd,bdf->btf", x_pad, take(w3))
    return jnp.einsum("btf,bfd->btd", h, take(w2))


# ------------------------------------------------------------ Pallas path

# F-tile and scoped-VMEM budget. One grid step holds three double-buffered
# [D, f_tile] weight tiles plus the [Bt, D] x/out blocks and the f32
# accumulator, over the compiler's 16 MiB default and far under a v5e
# core's 128 MiB. The tile is the widest multiple of 256 dividing F whose
# six weight buffers fit _TILE_BYTES: a grid step costs ~0.35 us whatever it
# moves, so int8 stacks at mixtral widths (D=4096) take 1024 (14 steps a
# block, not 56: 2.04 against 2.22 ms for 8 live blocks on a v5e), bf16
# ones 512, and the latent family's [7168, 2048] bf16 stacks 256.
_F_TILE = 256
_TILE_BYTES = 24 * 1024 * 1024
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _f_tiles(F: int, D: int, itemsize: int) -> tuple[int, int]:
    if F <= _F_TILE:
        return F, 1
    if F % _F_TILE:
        raise ValueError(f"expert hidden {F} must divide the F-tile {_F_TILE}")
    f_tile = max((t for t in range(_F_TILE, F + 1, _F_TILE)
                  if F % t == 0 and 6 * D * t * itemsize <= _TILE_BYTES),
                 default=_F_TILE)
    return f_tile, F // f_tile


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _block_maps(f_tiles: int):
    """Index maps of the (row-block, F-tile) grid both kernel variants run,
    over the two scalar-prefetched vectors ``block_expert`` [NB] and
    ``live_blocks`` [1]: ``rows`` for the block's [Bt, D] input, ``up`` /
    ``down`` / ``whole`` for an expert's [D, Ft] tile (and its [1, Ft] scale
    row), its [Ft, D] tile and its [1, D] scale row. A block at or past
    ``live_blocks`` is padding beyond every group: its maps ask for what the
    last live step left resident, so it fetches nothing."""
    def last_live(live):
        return jnp.maximum(live[0] - 1, 0)

    def rows(b, f, be, live):
        return (jnp.minimum(b, last_live(live)), 0, 0)

    def tile_of(b, f, be, live):
        dead = b >= live[0]
        return (be[jnp.where(dead, last_live(live), b)],
                jnp.where(dead, f_tiles - 1, f))

    def up(b, f, be, live):
        e, ft = tile_of(b, f, be, live)
        return (e, 0, ft)

    def down(b, f, be, live):
        e, ft = tile_of(b, f, be, live)
        return (e, ft, 0)

    def whole(b, f, be, live):
        return (tile_of(b, f, be, live)[0], 0, 0)

    return rows, up, down, whole


def _moe_block_kernel(block_expert_ref, live_ref, x_ref, *refs, act: str,
                      quantized: bool):
    """One (row-block, F-tile) step: h = act(x@w1_f) * (x@w3_f); the
    [Bt, D] output accumulates h @ w2_f in VMEM scratch across F-tiles.
    The expert's weight tiles arrive via the BlockSpec index maps reading
    the scalar-prefetched ``block_expert`` — the kernel body never
    gathers. Operands go to the MXU in the activations' dtype; sums are
    f32. Int8 stacks (``quantized``: each weight ref is followed by its
    scale row) stay int8-sized in HBM and on the way to VMEM; the scales
    apply per F-tile on the hidden ([1, Ft]) and once on the output
    ([1, D]: s2 factors out of the F sum). A block at or past
    ``live_ref[0]`` computes nothing, fetches nothing (:func:`_block_maps`)
    and writes zeros."""
    if quantized:
        w1_ref, s1_ref, w3_ref, s3_ref, w2_ref, s2_ref, o_ref, acc_ref = refs
    else:
        w1_ref, w3_ref, w2_ref, o_ref, acc_ref = refs
        s1_ref = s3_ref = s2_ref = None
    b, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(b < live_ref[0])
    def _compute():
        x = x_ref[...]                                # [Bt, D]

        def up(w_ref, s_ref):                         # -> [Bt, Ft] f32
            h = _dot(x, w_ref[...].astype(x.dtype))
            return h * s_ref[...].astype(jnp.float32) if quantized else h

        h = _act(up(w1_ref, s1_ref), act) * up(w3_ref, s3_ref)
        acc_ref[...] += _dot(h.astype(x.dtype),
                             w2_ref[...].astype(x.dtype))       # [Bt, D]

    @pl.when(f == pl.num_programs(1) - 1)
    def _finish():
        out = acc_ref[...]
        if quantized:
            out = out * s2_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block", "interpret"))
def _expert_blocks_pallas(x_pad: jax.Array, w1, w3, w2,
                          block_expert: jax.Array,
                          live_blocks: jax.Array | None = None,
                          act: str = "silu", block: int = 128,
                          interpret: bool = False) -> jax.Array:
    """[NB, Bt, D] rows through their owning expert's FFN: the one grid,
    skip rule and kernel body of both stack dtypes (``grouped_moe`` in a
    trace for full-precision stacks, ``grouped_moe_q8`` for {"q", "s"} int8
    ones). Jitted, so a step program traces it once a shape, not once a
    layer."""
    quantized = isinstance(w1, dict)
    NB = block_expert.shape[0]
    D = x_pad.shape[-1]
    stack = w1["q"] if quantized else w1
    f_tile, f_tiles = _f_tiles(stack.shape[-1], D, stack.dtype.itemsize)
    if live_blocks is None:
        live_blocks = jnp.full((1,), NB, jnp.int32)
    rows, up, down, whole = _block_maps(f_tiles)

    operands, specs = [x_pad], [pl.BlockSpec((None, block, D), rows)]
    for w, shape, tile, scale in ((w1, (D, f_tile), up, up),
                                  (w3, (D, f_tile), up, up),
                                  (w2, (f_tile, D), down, whole)):
        operands.append(w["q"] if quantized else w)
        specs.append(pl.BlockSpec((None, *shape), tile))
        if quantized:
            # [E, C] -> [E, 1, C]: a (1, tile) block is legal only where 1
            # is the array's own dim
            operands.append(w["s"][:, None, :])
            specs.append(pl.BlockSpec((None, 1, shape[-1]), scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_expert, live_blocks
        grid=(NB, f_tiles),
        in_specs=specs,
        out_specs=pl.BlockSpec((None, block, D),
                               lambda b, f, be, live: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_moe_block_kernel, act=act, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NB, block, D), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="grouped_moe_q8" if quantized else "grouped_moe",
        interpret=interpret,
    )(block_expert, live_blocks, *operands)


# ----------------------------------------------------------- public entry

def moe_ffn_grouped(params: dict[str, Any], x: jax.Array, config,
                    act: str = "silu", impl: str = "xla",
                    block: int = 128, interpret: bool = False,
                    valid: jax.Array | None = None) -> jax.Array:
    """Dropless grouped MoE FFN of a model that holds ALL its experts here,
    exact-parity with ``moe_ffn_dense_mask``. x: [B, S, D] -> [B, S, D].

    ``impl``: "xla" (gathered-weights einsum — every backend; for large
    models the gather MATERIALIZES [NB, D, F] weights in HBM, so it is
    the reference semantics, not the serving path) or "pallas" (TPU
    kernel, int8 and full-precision stacks — weight tiles DMA
    per-block via scalar prefetch, nothing materializes;
    ``interpret=True`` runs it on CPU for tests).

    ``valid`` [B, S] (optional): False marks a padding token of the step's
    bucket. Its pairs get no row (the plan reads them as held elsewhere), so
    the live blocks follow the tokens and not the bucket; its output is zero.
    """
    from ..parallel.moe import router_probs

    B, S, D = x.shape
    flat = x.reshape(-1, D)
    probs = router_probs(params["router"], flat)                # [T, E]
    ids, gates = top_k_gates(probs, config.top_k)
    if valid is not None:
        ids = jnp.where(valid.reshape(-1, 1), ids, probs.shape[1])
    plan = plan_sorted_blocks(ids, gates, probs.shape[1], block)
    return experts_grouped(params, flat, plan, act=act, impl=impl,
                           block=block, interpret=interpret,
                           gather_back=True).reshape(B, S, D)


def experts_grouped(params: dict[str, Any], flat: jax.Array,
                    plan: dict[str, jax.Array], act: str = "silu",
                    impl: str = "xla", block: int = 128,
                    interpret: bool = False,
                    gather_back: bool = False) -> jax.Array:
    """The held experts' weighted part of every token, from a routing plan
    (:func:`plan_sorted_blocks`): flat [T, D] -> [T, D]. ``params`` holds the
    stacks ``w1``/``w3`` [E, D, F] and ``w2`` [E, F, D] of the E experts the
    plan's ids index.

    The way back, the caller's to say because only it knows where most pairs
    land: ``gather_back`` has each token gather its k rows by the plan's
    inverse (``pair_row``) and sum them weighted in float32 — right where
    nearly every pair has a row here (all experts held); the default
    scatter-adds the weighted rows to their tokens — right where most pairs
    are held elsewhere and T·k gathered rows would be mostly fill."""
    D = flat.shape[-1]
    x_pad = flat[plan["sorted_token"]]                          # [NP, D]
    if not gather_back:
        # group padding reads token 0's row; a gathered way back never
        # looks at such a row, the scatter multiplies it by zero
        x_pad = x_pad * plan["row_valid"][:, None].astype(flat.dtype)
    NB = plan["block_expert"].shape[0]

    if impl == "pallas":
        out_blocks = _expert_blocks_pallas(
            x_pad.reshape(NB, block, D), params["w1"], params["w3"],
            params["w2"], plan["block_expert"], plan["live_blocks"],
            act=act, block=block, interpret=interpret)
    else:
        out_blocks = _expert_blocks_xla(
            x_pad.reshape(NB, block, D), params["w1"], params["w3"],
            params["w2"], plan["block_expert"], act)
    out_rows = out_blocks.reshape(NB * block, D)
    if gather_back:
        # a slot at a time: a [T, k, D] gather would tile k on sublanes
        mine = [out_rows.at[plan["pair_row"][:, slot]].get(
                    mode="fill", fill_value=0).astype(jnp.float32)
                * plan["pair_gates"][:, slot, None]
                for slot in range(plan["pair_row"].shape[1])]   # k x [T, D]
        return sum(mine[1:], mine[0]).astype(flat.dtype)
    weighted = out_rows * (plan["gates"]
                           * plan["row_valid"])[:, None].astype(flat.dtype)
    return jnp.zeros_like(flat).at[plan["sorted_token"]].add(weighted)


def grouped_flops(T: int, top_k: int, n_experts: int, dim: int,
                  hidden: int, block: int = 128) -> dict[str, float]:
    """FFN FLOPs accounting: grouped vs dense-mask vs ideal (router
    excluded from all three). Used by tests to pin the ~k/E claim."""
    per_row = 3 * 2 * dim * hidden          # w1, w3, w2 matmuls
    NB = -(-T * top_k // block) + n_experts
    return {
        "dense_mask": float(n_experts * T * per_row),
        "grouped": float(NB * block * per_row),
        "ideal": float(T * top_k * per_row),
    }
