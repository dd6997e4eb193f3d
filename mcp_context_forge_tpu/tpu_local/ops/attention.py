"""Causal (flash) attention.

- ``flash_attention_pallas``: blockwise online-softmax kernel for TPU
  (per /opt/skills/guides/pallas_guide.md patterns): grid over
  (batch*heads, q blocks), inner fori_loop over k blocks up to the causal
  frontier, running max/denominator in VMEM scratch. HBM traffic is O(S·d)
  per block instead of materializing the S×S score matrix.
- ``select_prefill_attention`` / ``select_paged_attention``: THE place that
  says which implementation a step runs — the kernels on a TPU mesh, the jnp
  references elsewhere (CPU CI / virtual mesh), identical numerics contract
  (fp32 accumulation).
- ``causal_attention``: dispatcher over the resolved implementation.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

logger = logging.getLogger(__name__)

NEG_INF = -1e30
FLASH_BLOCK = 128          # q and k tokens a grid step of the flash kernel holds


# ------------------------------------------------------------------- reference

def block_last(positions: jax.Array, mask_block: int) -> jax.Array:
    """The position a query at ``positions`` masks on under a block-causal
    mask of ``mask_block`` positions a block: its block's LAST position, so
    that ``k_pos <= block_last(q_pos)`` lets a query see its whole block and
    everything before it (RoPE keeps the true position). 1 is the causal
    mask and returns ``positions`` itself, untouched; -1 (padding) stays -1."""
    if mask_block == 1:
        return positions
    if mask_block & (mask_block - 1) == 0:
        return positions | (mask_block - 1)
    return jnp.where(positions < 0, positions,
                     positions // mask_block * mask_block + mask_block - 1)


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        valid: jax.Array | None = None,
                        mask_block: int = 1) -> jax.Array:
    """q: [B,S,H,hd]; k/v: [B,S,KV,hd] (GQA); valid: [B,S] bool;
    ``mask_block``: :func:`block_last` (1: causal). -> [B,S,H,hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    qf = q.astype(jnp.float32).reshape(B, S, KV, group, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qf, kf) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    if mask_block != 1:
        at = jnp.arange(S)
        causal = at[None, :] <= block_last(at, mask_block)[:, None]
    mask = causal[None, None, None]
    if valid is not None:
        mask = mask & valid[:, None, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, vf)
    return out.reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------- pallas

def _flash_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, *, block_q: int,
                  block_k: int, head_dim: int, mask_block: int = 1):
    """One (batch, head, q-block) program. Refs:
    q [block_q, hd]; k/v [S, hd] (the head's kv head); valid [1, S] int32;
    o [block_q, hd]. ``mask_block`` divides ``block_q``, so a query's block
    ends inside its q block and the k blocks walked are the causal ones."""
    q_start = pl.program_id(2) * block_q

    q = q_ref[...]
    q_positions = block_last(
        q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0),
        mask_block)

    num_k_blocks = (q_start + block_q + block_k - 1) // block_k

    def body(kb, carry):
        acc, row_max, row_sum = carry
        k_start = pl.multiple_of(kb * block_k, block_k)
        k_tile = k_ref[pl.ds(k_start, block_k), :]
        v_tile = v_ref[pl.ds(k_start, block_k), :]
        scores = jax.lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / math.sqrt(head_dim)
        k_positions = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = ((k_positions <= q_positions)
                & (valid_ref[:, pl.ds(k_start, block_k)] != 0))
        scores = jnp.where(mask, scores, NEG_INF)              # [bq, bk]
        tile_max = jnp.max(scores, axis=1, keepdims=True)
        new_max = jnp.maximum(row_max, tile_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(scores - new_max)
        new_sum = row_sum * correction + jnp.sum(probs, axis=1, keepdims=True)
        new_acc = acc * correction + jnp.dot(
            probs.astype(v_tile.dtype), v_tile,
            preferred_element_type=jnp.float32)
        return new_acc, new_max, new_sum

    acc = jnp.zeros((block_q, head_dim), dtype=jnp.float32)
    row_max = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    row_sum = jnp.zeros((block_q, 1), dtype=jnp.float32)
    acc, row_max, row_sum = jax.lax.fori_loop(0, num_k_blocks, body,
                                              (acc, row_max, row_sum))
    o_ref[...] = (acc / jnp.maximum(row_sum, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret",
                                             "mask_block"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           valid: jax.Array, block_q: int = FLASH_BLOCK,
                           block_k: int = FLASH_BLOCK, interpret: bool = False,
                           mask_block: int = 1) -> jax.Array:
    """q: [B,S,H,hd]; k/v: [B,S,KV,hd] (GQA: the index map hands each q
    head its kv head, so the repeated heads never materialize);
    valid: [B,S] bool; ``mask_block``: :func:`block_last` (1: causal)."""
    B, S, H, hd = q.shape
    group = H // k.shape[2]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"seq {S} must divide blocks ({block_q}, {block_k})")
    if block_q % mask_block:
        raise ValueError(f"mask_block {mask_block} must divide the q block "
                         f"{block_q}")
    qt = q.transpose(0, 2, 1, 3)                          # [B, H, S, hd]
    kt = k.transpose(0, 2, 1, 3)                          # [B, KV, S, hd]
    vt = v.transpose(0, 2, 1, 3)
    valid_rows = valid.astype(jnp.int32)[:, None, :]      # [B, 1, S]

    kv_spec = pl.BlockSpec((None, None, S, hd),
                           lambda b, h, qb: (b, h // group, 0, 0))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          head_dim=hd, mask_block=mask_block),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        grid=(B, H, S // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, qb: (b, h, qb, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((None, 1, S), lambda b, h, qb: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, qb: (b, h, qb, 0)),
        name="flash_attention",
        interpret=interpret,
    )(qt, kt, vt, valid_rows)
    return out.transpose(0, 2, 1, 3)


# ------------------------------------------------------------------ dispatcher

def on_tpu(mesh) -> bool:
    """True when ``mesh`` was built from TPU devices. Kernel-or-reference
    follows the devices the ENGINE was given (its mesh), never whatever
    ``jax.devices()`` finds: an engine on CPU devices beside a chip, or one
    traced for a described chip, takes the path of its own devices."""
    return mesh is not None and mesh.devices.flat[0].platform == "tpu"


def _kv_heads_split(mesh, n_kv_heads: int) -> bool:
    """The kernels run per kv-head shard under shard_map, so the heads
    must divide the ``model`` axis (else the pool replicates and the
    per-shard q-head -> kv-head grouping no longer lines up)."""
    return n_kv_heads % mesh.shape.get("model", 1) == 0


# whole-sequence K and V blocks of one head, double-buffered, that the
# flash kernel may hold in VMEM beside its q/o blocks and score tiles
_FLASH_KV_VMEM_BYTES = 8 * 1024 * 1024


def _kernel_unless(why: str, what: str, fallback: str) -> str:
    """"pallas", or ``fallback`` when ``why`` names a refusal — which on a
    TPU is logged (once per compiled shape, since tracing is), never
    silent."""
    if not why:
        return "pallas"
    logger.warning("%s on TPU: kernel refused (%s); the XLA %s path is in "
                   "use", what, why, fallback)
    return fallback


def select_prefill_attention(impl: str, mesh, seq: int, head_dim: int,
                             n_kv_heads: int, itemsize: int = 2) -> str:
    """Resolve ``impl="auto"`` for one prefill shape, at trace time: the
    flash kernel on a TPU mesh where the shape can take it, the jnp
    reference elsewhere."""
    if impl != "auto":
        return impl
    if not on_tpu(mesh):
        return "reference"
    why = ""
    if seq % FLASH_BLOCK or head_dim % 128:
        why = f"S={seq} and head_dim={head_dim} must be multiples of 128"
    elif 4 * seq * head_dim * itemsize > _FLASH_KV_VMEM_BYTES:
        why = f"K/V of S={seq} exceed the kernel's VMEM budget"
    elif not _kv_heads_split(mesh, n_kv_heads):
        why = f"{n_kv_heads} kv heads do not divide the model axis"
    return _kernel_unless(why, "prefill attention", "reference")


def select_paged_attention(mesh, head_dim: int, page_size: int,
                           n_kv_heads: int, quantized: bool) -> str:
    """"pallas" (ops/paged_attention.py) or "gather" (the jnp reference
    over gather_kv) for one decode / history-prefill shape, at trace time.
    Same rule as :func:`select_prefill_attention`. Int8 pools need
    page_size % 32 == 0 (the int8 sublane tile is 32 vs 8 for wider
    dtypes)."""
    if not on_tpu(mesh):
        return "gather"
    min_page = 32 if quantized else 8
    why = ""
    if head_dim % 128:
        why = f"head_dim={head_dim} must be a multiple of 128"
    elif page_size % min_page:
        why = f"page_size={page_size} must be a multiple of {min_page}"
    elif not _kv_heads_split(mesh, n_kv_heads):
        why = f"{n_kv_heads} kv heads do not divide the model axis"
    return _kernel_unless(why, "paged attention", "gather")


def on_model_axis(kernel, mesh, in_specs, out_specs):
    """Run ``kernel`` once per ``model``-axis shard of its operands.
    A pallas_call under plain jit with TP-sharded operands leaves XLA to
    refuse the partitioning or gather every operand to every chip; under
    shard_map each chip runs the kernel on the heads it holds."""
    if mesh is None or mesh.shape.get("model", 1) == 1:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array | None = None,
                     impl: str = "reference", mesh=None,
                     mask_block: int = 1) -> jax.Array:
    """Dispatch: impl in {pallas, reference, ring, ulysses} ("auto" is
    resolved by the caller that knows its mesh — see
    :func:`select_prefill_attention`). ``mask_block`` > 1 is the
    block-causal mask (:func:`block_last`), which the kernel and the
    reference take and the sequence-parallel paths do not.

    ring/ulysses are the sequence-parallel paths (SURVEY.md §5.7): the
    sequence dim is sharded over the mesh's ``model`` axis via shard_map —
    ring rotates K/V blocks over ICI with online-softmax merging; Ulysses
    reshards seq→heads with one all_to_all each way. Requires ``mesh`` and
    S divisible by the axis size.
    """
    B, S, H, hd = q.shape
    if valid is None:
        valid = jnp.ones((B, S), dtype=bool)
    if impl in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(f"attn impl {impl!r} requires a mesh")
        if mask_block != 1:
            raise ValueError(f"attn impl {impl!r} has no block-causal mask")
        from ..parallel.ring_attention import (make_ring_attention,
                                               make_ulysses_attention)
        # GQA k/v stay at KV width: the SP bodies expand per device, so the
        # wire (ppermute/all_to_all) never carries the repeated heads
        axis_size = mesh.shape.get("model", 1)
        if impl == "ulysses" and (H % axis_size != 0
                                  or k.shape[2] % axis_size != 0):
            # Ulysses reshards heads across the axis, so both q and kv head
            # counts must divide it; ring has no such constraint — fall
            # back (same numerics)
            impl = "ring"
        maker = make_ring_attention if impl == "ring" else make_ulysses_attention
        return maker(mesh, axis_name="model")(q, k, v, valid)
    if impl == "pallas":
        heads = P(None, None, "model", None)
        kernel = functools.partial(flash_attention_pallas,
                                   mask_block=mask_block)
        return on_model_axis(kernel, mesh,
                             (heads, heads, heads, P()), heads)(q, k, v, valid)
    if impl != "reference":
        raise ValueError(f"unknown attention impl {impl!r}")
    return attention_reference(q, k, v, valid, mask_block)
