"""What the chip's compiler says, asked without a chip.

The TPU compiler is installed here and compiles for a device that is
DESCRIBED, not attached (``jax.experimental.topologies``): every Pallas kernel
in ``tpu_local/ops`` at real widths (Llama-3-8B / Mistral-7B head geometry,
Mixtral-8x7B expert widths), about two seconds each. Interpret-mode tests
cannot see what these see — block shapes the lowering refuses, primitives
Mosaic lacks, scoped-VMEM overruns. A compile that passes is not a chip run:
results and times come from ``chip_smoke.py`` on the chip.

Plus the CPU rehearsal of ``chip_smoke.py``'s phase functions at tiny sizes,
and the check that the script refuses to run without a TPU.
"""

import asyncio
import dataclasses
import json
import math
import os
import re
import subprocess
import sys
import unittest.mock as mock
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from mcp_context_forge_tpu.tpu_local.kv import (LatentKVState, stored_width,
                                                write_latent_kv)
from mcp_context_forge_tpu.tpu_local.ops import (attention, gated_delta,
                                                 grouped_moe, ssd)
from mcp_context_forge_tpu.tpu_local.ops import mla_attention as mla
from mcp_context_forge_tpu.tpu_local.ops import paged_attention as paged

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

KV, G, HD, PAGE = 8, 4, 128, 128          # mistral-7b / llama3-8b heads
LAYERS, PAGES = 32, 384
E, D, F, BLOCK = 8, 4096, 14336, 128      # mixtral-8x7b experts


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip as a sharding for abstract arguments."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def _paged_case(kv_dtype, chunk: int | None, batch: int, table: int,
                kv_heads: int = KV):
    """(fn, shapes) for the paged kernel: decode when ``chunk`` is None,
    else ``chunk`` queries per row."""
    pool = ((LAYERS, PAGES, PAGE, kv_heads, HD), kv_dtype)
    shapes = [pool, pool, ((batch, table), jnp.int32)]
    if chunk is None:
        fn = paged.paged_decode_attention_pallas
        shapes = [((batch, kv_heads, G, HD), jnp.bfloat16)] + shapes + [
            ((batch,), jnp.int32)]
    else:
        fn = paged.paged_chunk_attention_pallas
        shapes = [((batch, chunk, kv_heads, G, HD), jnp.bfloat16)] + shapes + [
            ((batch, chunk), jnp.int32)]
    if kv_dtype == jnp.int8:
        scale = ((LAYERS, PAGES, kv_heads), jnp.bfloat16)
        return (lambda *a: fn(*a[:5], layer=7, k_scales=a[5], v_scales=a[6]),
                shapes + [scale, scale])
    return partial(fn, layer=7), shapes


def _flash_case(seq: int):
    q = ((2, seq, KV * G, HD), jnp.bfloat16)
    kv = ((2, seq, KV, HD), jnp.bfloat16)
    return attention.flash_attention_pallas, [q, kv, kv, ((2, seq), jnp.bool_)]


def _moe_case(quantized: bool, n_blocks: int = 2048 * 2 // BLOCK + E,
              live: bool = False):
    """The row-block kernel over bf16 or int8 stacks; ``live``: with the
    count of live blocks given (dead ones skip)."""
    x = ((n_blocks, BLOCK, D), jnp.bfloat16)
    tail = [((n_blocks,), jnp.int32)] + ([((1,), jnp.int32)] if live else [])
    if not quantized:
        up, down = ((E, D, F), jnp.bfloat16), ((E, F, D), jnp.bfloat16)
        return (partial(grouped_moe._expert_blocks_pallas, block=BLOCK),
                [x, up, up, down, *tail])
    up = [((E, D, F), jnp.int8), ((E, F), jnp.bfloat16)]
    down = [((E, F, D), jnp.int8), ((E, D), jnp.bfloat16)]

    def fn(x, q1, s1, q3, s3, q2, s2, *tail):
        return grouped_moe._expert_blocks_pallas(
            x, {"q": q1, "s": s1}, {"q": q3, "s": s3}, {"q": q2, "s": s2},
            *tail, block=BLOCK)
    return fn, [x, *up, *up, *down, *tail]


# deepseek-v3.2-d5-ep16.longctx-closed: 5 layers, 1152 pages of 128, latent
# 512 + 64 stored in 640 lanes (kv.stored_width), selector 64 heads x 128,
# top-2048; chunk rounds of 2 x 1024 and decode steps of 8 rows over the full
# 128-page table; 16 held experts
DS_LAYERS, DS_PAGES, DS_TABLE, DS_LATENT, DS_VALUE = 5, 1152, 128, 640, 512
DS_DECLARED = 576
DS_HEADS, DS_IDX_HEADS, DS_IDX_DIM, DS_TOPK = 128, 64, 128, 2048


def _mla_attention_case(chunk: int | None, table: int = DS_TABLE):
    """Chunk round (``chunk`` queries a row, 2 rows) or decode (8 rows) over
    a block table of ``table`` pages (a context or history bucket)."""
    pool = ((DS_LAYERS, DS_PAGES, PAGE, DS_LATENT), jnp.bfloat16)
    fn = partial(mla.mla_paged_attention_pallas, layer=3, value_dim=DS_VALUE)
    if chunk is None:
        B = 8
        return fn, [((B, 1, DS_HEADS, DS_LATENT), jnp.bfloat16),
                    ((B, 1, table * PAGE), jnp.float32), pool,
                    ((B, table), jnp.int32), ((B, 1), jnp.int32)]
    B = 2
    return fn, [((B, DS_HEADS, chunk, DS_LATENT), jnp.bfloat16),
                ((B, chunk, table * PAGE), jnp.float32), pool,
                ((B, table), jnp.int32),
                ((B, chunk // mla._ATTN_QUERY_TILE), jnp.int32)]


def _mla_verify_case(table: int):
    """joyai-llm-flash-d5-ep4.reason-closed: a verify step of 32 rows x 2
    query positions x 32 heads over a context bucket of ``table`` pages, a
    bias row a POSITION (``group_bias``), six layers of 2304 latent pages
    (stored width)."""
    B, K, H = 32, 2, 32
    fn = partial(mla.mla_paged_attention_pallas, layer=5, value_dim=DS_VALUE,
                 group_bias=True)
    return fn, [((B, K, H, DS_LATENT), jnp.bfloat16),
                ((B, K, table * PAGE), jnp.float32),
                ((6, 2304, PAGE, DS_LATENT), jnp.bfloat16),
                ((B, table), jnp.int32), ((B, 1), jnp.int32)]


def _index_case(S: int = 1024):
    B = 2
    return (partial(mla.sparse_index_scores_pallas, layer=3),
            [((B, S, DS_IDX_HEADS, DS_IDX_DIM), jnp.bfloat16),
             ((B, S, DS_IDX_HEADS), jnp.float32),
             ((DS_LAYERS, DS_PAGES, PAGE, DS_IDX_DIM), jnp.bfloat16),
             ((B, DS_TABLE), jnp.int32), ((B, S), jnp.int32)])


def _select_case(rows: int):
    return (partial(mla.sparse_select_pallas, k=DS_TOPK),
            [((rows, DS_TABLE * PAGE), jnp.float32)])


def _held_experts_case():
    """16 held experts x width 2048 x hidden 7168, a chunk round's plan: every
    one of 2048 x 8 pairs may land here."""
    held, dim, width = 16, 7168, 2048
    n_blocks = 2048 * 8 // BLOCK + held
    up, down = ((held, dim, width), jnp.bfloat16), ((held, width, dim), jnp.bfloat16)
    return (partial(grouped_moe._expert_blocks_pallas, block=BLOCK),
            [((n_blocks, BLOCK, dim), jnp.bfloat16), up, up, down,
             ((n_blocks,), jnp.int32), ((1,), jnp.int32)])


def _gated_delta_case(batch: int, seq: int):
    """olmo-hybrid-7b.chat: 30 heads of a 96 x 192 float32 state, 24 linear
    layers x 33 rows; decode steps of 32 rows (the token walk), prefills of up
    to 4 x 512 and the half program's 1 x 256 (the chunkwise body since PR 52:
    a grid step holds all 30 heads of a 64-token chunk: a q and a k block of
    1.05 MB each (a token's [30, 96] padded to [32, 128]), a v and an o block
    of 1.47 MB ([64, 5760], as the state lies), the row's whole 2.21 MB state
    in and out, all double-buffered, and 2 MB of heads-first scratch: 21 MB
    against the limit of 48 before Mosaic's own temporaries). A pass here is not the chip's word on scoped
    VMEM (``_kda_case``): PR 52 compiled the cell's four dense prefill
    programs ([1, 256], [1, 512], [2, 512], [4, 512]) and its history
    programs ON the chip, inside the cell's warm-up, before measuring; what
    this compile did say first is that Mosaic's strided load wants a last
    dimension of exactly 128 (refused at 96, 192 and 256), which is why the
    body turns its blocks heads-first."""
    H, dk, dv = 30, 96, 192
    f32, i32 = jnp.float32, jnp.int32
    shapes = [((batch, seq, H, dk), f32)] * 2 + [((batch, seq, H, dv), f32)] \
        + [((batch, seq, H), f32)] * 2 + [((24, 33, dk, H * dv), f32)] \
        + [((batch,), i32)] * 3
    return partial(gated_delta.gated_delta_pallas, layer=7), shapes


def _kda_case(batch: int, seq: int):
    """solar-open2-d8-ep8.mixedctx-open: the channel-decay form, 64 heads of a
    128 x 128 float32 state, 6 KDA layers x 33 rows; decode steps of 32 rows
    (the token walk: a row's whole 4.19 MB state in and out of VMEM beside one
    token's 392-row tile), chunk rounds of up to 2 x 1024 and a half bucket of
    512 (the chunkwise body since PR 51: 8 heads a grid step, so 0.5 MB of
    state in and out, four 256 KB token blocks of [64, 8, 128] and one of
    output, all double-buffered: about 5 MB against the limit of 48, whatever
    the number of heads). (A pass here is not the chip's word:
    the walk at 64 tokens a tile passed this compile, and the chip's, inside a
    step program, refused 48.5 MB of scoped VMEM against 48; PR 51 ran the
    chunkwise body inside the chunk round on the chip.)"""
    H, dk, dv = 64, 128, 128
    f32, i32 = jnp.float32, jnp.int32
    shapes = [((batch, seq, H, dk), f32)] * 2 + [((batch, seq, H, dv), f32)] \
        + [((batch, seq, H, dk), f32), ((batch, seq, H), f32),
           ((6, 33, dk, H * dv), f32)] + [((batch,), i32)] * 3
    return partial(gated_delta.gated_delta_pallas, layer=3), shapes


def _ssd_case(batch: int, seq: int):
    """granite-4.0-h-micro.longgen-open: 64 heads of 64 over ONE group of 128
    B and C channels, a 128 x 4096 float32 state a row, 36 Mamba layers x 65
    rows; decode steps of 64 rows (``ssd_step``: a row's 2.1 MB state in and
    out, double-buffered, beside two [2, 4096] token rows: 8.5 MB), prefills
    of up to 4 x 512 and the half program's 1 x 256 (``ssd_chunk``: the state
    in and out double-buffered 8.4 MB, a chunk's [64, 4096] x, y and dt x
    blocks 5 MB, the 0/1 spread matrix 2 MB, ~7 MB of [64, 4096] and
    [192, 4096] temporaries: ~22 MB against the limit of 48). A pass here is
    not the chip's word on scoped VMEM (``_kda_case``): the cell's warm-up
    compiles both inside its step programs on the chip."""
    H, P, N = 64, 64, 128
    f32, i32 = jnp.float32, jnp.int32
    shapes = [((batch, seq, H * P), f32), ((batch, seq, H), f32), ((H,), f32)] \
        + [((batch, seq, N), f32)] * 2 + [((36, 65, N, H * P), f32)] \
        + [((batch,), i32)] * 3
    return partial(ssd.ssd_pallas, layer=7), shapes


def _solar_moe_case(tokens: int, block: int):
    """The row-block kernel over the 40 held int8 experts of 4096 x 1280: a
    step's plan holds a block for every one of its tokens x 8 pairs (any may
    land here) and a spare one a held expert, at the row-block the published
    320 give the step (``llama.expert_block``)."""
    held, dim, width = 40, 4096, 1280
    n_blocks = -(-tokens * 8 // block) + held
    up = [((held, dim, width), jnp.int8), ((held, width), jnp.bfloat16)]
    down = [((held, width, dim), jnp.int8), ((held, dim), jnp.bfloat16)]

    def fn(x, q1, s1, q3, s3, q2, s2, *tail):
        return grouped_moe._expert_blocks_pallas(
            x, {"q": q1, "s": s1}, {"q": q3, "s": s3}, {"q": q2, "s": s2},
            *tail, block=block)
    return fn, [((n_blocks, block, dim), jnp.bfloat16), *up, *up, *down,
                ((n_blocks,), jnp.int32), ((1,), jnp.int32)]


def _solar_paged_case(chunk: int | None, batch: int):
    """The paged kernel over the 2 GQA layers' 4352 pages, 8 kv heads x 8
    query heads each, the full 132-page table of a 16896-token sequence."""
    pool = ((2, 4352, PAGE, 8, HD), jnp.bfloat16)
    shapes = [pool, pool, ((batch, 132), jnp.int32)]
    if chunk is None:
        return (partial(paged.paged_decode_attention_pallas, layer=1),
                [((batch, 8, 8, HD), jnp.bfloat16)] + shapes
                + [((batch,), jnp.int32)])
    return (partial(paged.paged_chunk_attention_pallas, layer=1),
            [((batch, chunk, 8, 8, HD), jnp.bfloat16)] + shapes
            + [((batch, chunk), jnp.int32)])


def _granite_paged_case(chunk: int | None, batch: int):
    """granite-4.0-h-micro.longgen-open: the paged kernel over the 4 attention
    layers' 1025 pages, 8 kv heads x 4 query heads of 64 STORED in 128 lanes
    (``GraniteHybridConfig.kv_head_dim``), the 16-page table of a 2048-token
    sequence, at the decode width of 64 and a chunk round's 512-query tile."""
    pool = ((4, 1025, PAGE, 8, HD), jnp.bfloat16)
    shapes = [pool, pool, ((batch, 16), jnp.int32)]
    if chunk is None:
        return (partial(paged.paged_decode_attention_pallas, layer=2),
                [((batch, 8, 4, HD), jnp.bfloat16)] + shapes
                + [((batch,), jnp.int32)])
    return (partial(paged.paged_chunk_attention_pallas, layer=2),
            [((batch, chunk, 8, 4, HD), jnp.bfloat16)] + shapes
            + [((batch, chunk), jnp.int32)])


def _hybrid_paged_case(chunk: int | None, batch: int, table: int):
    """The paged kernel over the hybrid's pool: 8 attending layers, 32 kv
    heads a page (30 padded to whole tiles), one query head a kv head."""
    pool = ((8, 256, PAGE, 32, HD), jnp.bfloat16)
    if chunk is None:
        return (partial(paged.paged_decode_attention_pallas, layer=3),
                [((batch, 32, 1, HD), jnp.bfloat16), pool, pool,
                 ((batch, table), jnp.int32), ((batch,), jnp.int32)])
    return (partial(paged.paged_chunk_attention_pallas, layer=3),
            [((batch, chunk, 32, 1, HD), jnp.bfloat16), pool, pool,
             ((batch, table), jnp.int32), ((batch, chunk), jnp.int32)])


# sdar-30b-a3b-d12.chat: 4 kv heads of 8 query heads, 12 layers x 512 pages;
# 128 experts of 2048 x 768 top-8
SDAR_KV, SDAR_G, SDAR_E, SDAR_D, SDAR_F = 4, 8, 128, 2048, 768


def _sdar_flash_case(batch: int, seq: int = 512):
    """A prefill's flash kernel under the block-causal mask (blocks of 4)."""
    q = ((batch, seq, SDAR_KV * SDAR_G, HD), jnp.bfloat16)
    kv = ((batch, seq, SDAR_KV, HD), jnp.bfloat16)
    return (partial(attention.flash_attention_pallas, mask_block=4),
            [q, kv, kv, ((batch, seq), jnp.bool_)])


def _sdar_paged_case(chunk: int, batch: int, table: int):
    """The paged chunk kernel at a block step's shape (4 positions a row, 32
    rows) and at a chunk round's query tile."""
    pool = ((12, 512, PAGE, SDAR_KV, HD), jnp.bfloat16)
    return (partial(paged.paged_chunk_attention_pallas, layer=7),
            [((batch, chunk, SDAR_KV, SDAR_G, HD), jnp.bfloat16), pool, pool,
             ((batch, table), jnp.int32), ((batch, chunk), jnp.int32)])


def _window_paged_case(chunk: int | None, batch: int):
    """trinity-mini-d8.mixedctx-open: the paged kernel under a window of
    2048 over the six window layers' rings (33 rows x 25 ring pages and the
    trash page, 4 kv heads, 8 query heads a kv head), the ring table the
    step program hands it (32 columns: the narrowest context bucket that
    holds a ring): a decode step of 32 rows, a chunk round's tile of 256
    queries x 2 rows."""
    pool = ((6, 33 * 25 + 1, PAGE, 4, HD), jnp.bfloat16)
    tables = ((batch, 32), jnp.int32)
    if chunk is None:
        return (partial(paged.paged_decode_attention_pallas, layer=5,
                        window=2048),
                [((batch, 4, 8, HD), jnp.bfloat16), pool, pool, tables,
                 ((batch,), jnp.int32)])
    return (partial(paged.paged_chunk_attention_pallas, layer=5, window=2048),
            [((batch, chunk, 4, 8, HD), jnp.bfloat16), pool, pool, tables,
             ((batch, chunk), jnp.int32)])


def _sdar_moe_case(tokens: int = 2048, block: int = BLOCK):
    """The row-block kernel over 128 int8 experts: a [4, 512] prefill's plan,
    2048 x 8 pairs in blocks of 128 rows, one spare block an expert; or a
    block step's (128 tokens) at the narrow row-block its width gives it."""
    n_blocks = tokens * 8 // block + SDAR_E
    up = [((SDAR_E, SDAR_D, SDAR_F), jnp.int8), ((SDAR_E, SDAR_F), jnp.bfloat16)]
    down = [((SDAR_E, SDAR_F, SDAR_D), jnp.int8), ((SDAR_E, SDAR_D), jnp.bfloat16)]

    def fn(x, q1, s1, q3, s3, q2, s2, *tail):
        return grouped_moe._expert_blocks_pallas(
            x, {"q": q1, "s": s1}, {"q": q3, "s": s3}, {"q": q2, "s": s2},
            *tail, block=block)
    return fn, [((n_blocks, block, SDAR_D), jnp.bfloat16), *up, *up, *down,
                ((n_blocks,), jnp.int32), ((1,), jnp.int32)]


# _history_tile(S, G=4) yields query tiles of 128..512 (and spec-verify
# chunks of spec_k=4); kv_heads=2 is one shard of a 1x4 TP mesh
KERNEL_CASES = {
    "paged_decode_bf16": lambda: _paged_case(jnp.bfloat16, None, 16, 8),
    "paged_decode_int8": lambda: _paged_case(jnp.int8, None, 16, 8),
    "paged_decode_bf16_tp_shard": lambda: _paged_case(
        jnp.bfloat16, None, 16, 8, kv_heads=2),
    "paged_chunk_bf16_tile128": lambda: _paged_case(jnp.bfloat16, 128, 4, 8),
    "paged_chunk_bf16_tile512": lambda: _paged_case(jnp.bfloat16, 512, 2, 16),
    "paged_chunk_bf16_spec4": lambda: _paged_case(jnp.bfloat16, 4, 16, 8),
    "paged_chunk_int8_tile128": lambda: _paged_case(jnp.int8, 128, 4, 8),
    # the benchmark cells' own shapes: mistral-7b.chat (32 slots, 8-page
    # bucket), mistral-7b.docs-closed decode steps and chunk rounds
    "paged_decode_bf16_cell_32x8": lambda: _paged_case(jnp.bfloat16, None, 32, 8),
    "paged_decode_bf16_cell_8x32": lambda: _paged_case(jnp.bfloat16, None, 8, 32),
    "paged_chunk_bf16_cell_tile512x32": lambda: _paged_case(
        jnp.bfloat16, 512, 2, 32),
    # a KV block of N pages a grid step (PR 30): the cells' other context
    # buckets (a block is the whole 4-page table; 2, 4 and 8 blocks a row),
    # int8 pages at a cell's widths (N scale blocks a pool), and a table
    # width 4 does not divide (blocks of 3)
    "paged_decode_bf16_cell_32x4": lambda: _paged_case(jnp.bfloat16, None, 32, 4),
    "paged_decode_bf16_cell_8x16": lambda: _paged_case(jnp.bfloat16, None, 8, 16),
    "paged_chunk_bf16_cell_tile512x4": lambda: _paged_case(
        jnp.bfloat16, 512, 2, 4),
    "paged_chunk_bf16_cell_tile512x8": lambda: _paged_case(
        jnp.bfloat16, 512, 2, 8),
    "paged_decode_int8_8x32": lambda: _paged_case(jnp.int8, None, 8, 32),
    "paged_chunk_int8_tile512x32": lambda: _paged_case(jnp.int8, 512, 2, 32),
    "paged_decode_bf16_table6": lambda: _paged_case(jnp.bfloat16, None, 8, 6),
    "flash_prefill_s512": lambda: _flash_case(512),
    "flash_prefill_s2048": lambda: _flash_case(2048),
    "mla_attention_cell_decode_8x128": lambda: _mla_attention_case(None),
    "mla_attention_cell_chunk_2x1024x128": lambda: _mla_attention_case(1024),
    # the cell's smallest and a middle decode bucket, the logits check's
    # 8-page history bucket, and a table 8 does not divide (blocks of 6)
    "mla_attention_cell_decode_8x4": lambda: _mla_attention_case(None, 4),
    "mla_attention_cell_decode_8x32": lambda: _mla_attention_case(None, 32),
    "mla_attention_cell_chunk_2x1024x8": lambda: _mla_attention_case(1024, 8),
    "mla_attention_decode_table12": lambda: _mla_attention_case(None, 12),
    "sparse_index_cell_chunk_2x1024x128": _index_case,
    "sparse_select_cell_chunk_rows": lambda: _select_case(2 * 1024),
    "sparse_select_cell_decode_rows": lambda: _select_case(8),
    "grouped_moe_cell_16_held_experts": _held_experts_case,
    "grouped_moe_bf16": lambda: _moe_case(False),
    "grouped_moe_int8": lambda: _moe_case(True),
    # mixtral-8x7b-d8.chat's prefills: [1, 2, 4] x 512 tokens top-2 are
    # 16, 24 and 40 row-blocks of which the live ones follow the prompts
    "grouped_moe_int8_cell_1x512": lambda: _moe_case(True, 16, live=True),
    "grouped_moe_int8_cell_2x512": lambda: _moe_case(True, 24, live=True),
    "grouped_moe_int8_cell_4x512": lambda: _moe_case(True, 40, live=True),
    # olmo-hybrid-7b.chat: the recurrence's two kernels, and the paged kernel
    # at 32 kv heads a page (decode buckets; a chunk round's 128-query tiles)
    "gated_delta_step_cell_32": lambda: _gated_delta_case(32, 1),
    "gated_delta_chunk_cell_4x512": lambda: _gated_delta_case(4, 512),
    "paged_decode_bf16_hybrid_32x8": lambda: _hybrid_paged_case(None, 32, 8),
    "paged_chunk_bf16_hybrid_tile128x8": lambda: _hybrid_paged_case(128, 4, 8),
    # sdar-30b-a3b-d12.chat: the masked prefill's flash kernel, the block
    # step's chunk kernel at both decode buckets, a chunk round's 256-query
    # tile, the grouped experts of a [4, 512] prefill, and those of a block
    # step (32 rows x 4 positions) at the 16-row block its width gives it
    "flash_prefill_block_mask_1x512": lambda: _sdar_flash_case(1),
    "flash_prefill_block_mask_4x512": lambda: _sdar_flash_case(4),
    "paged_chunk_bf16_block_step_32x4": lambda: _sdar_paged_case(4, 32, 4),
    "paged_chunk_bf16_block_step_32x8": lambda: _sdar_paged_case(4, 32, 8),
    "paged_chunk_bf16_sdar_tile256x8": lambda: _sdar_paged_case(256, 4, 8),
    "grouped_moe_int8_sdar_4x512": _sdar_moe_case,
    "grouped_moe_int8_sdar_block_step_b16": lambda: _sdar_moe_case(128, 16),
    # the half-length prefill programs of the cells (a lone prompt that fits
    # half its bucket, PR 40): the flash kernel at 256 (the GQA trunk, the
    # hybrid's attending layers, the block mask), the delta-rule chunk kernel
    # over one row of 256, a 256-token step's experts at the 16-row block the
    # rule gives it, and the latent family's two prefill kernels at 512
    "flash_prefill_half_s256": lambda: _flash_case(256),
    "flash_prefill_block_mask_half_1x256": lambda: _sdar_flash_case(1, 256),
    "gated_delta_chunk_half_1x256": lambda: _gated_delta_case(1, 256),
    "grouped_moe_int8_sdar_half_1x256_b16": lambda: _sdar_moe_case(256, 16),
    "mla_attention_half_chunk_2x512x8": lambda: _mla_attention_case(512, 8),
    # the latent kernel at verify width (PR 41): the narrowest and the widest
    # context bucket of the cell
    "mla_attention_verify_32x2x4": lambda: _mla_verify_case(4),
    "mla_attention_verify_32x2x64": lambda: _mla_verify_case(64),
    "sparse_index_half_chunk_2x512x128": lambda: _index_case(512),
    # the paged kernel with a lower bound on what a query sees (PR 43): the
    # window layers' calls of the cell, decode width and a chunk round's tile
    "paged_decode_bf16_window_32x32": lambda: _window_paged_case(None, 32),
    "paged_chunk_bf16_window_tile256x32": lambda: _window_paged_case(256, 2),
    # solar-open2-d8-ep8.mixedctx-open (PR 50): the channel-decay recurrence's
    # two kernels, the held experts at a decode step's, a half prefill's and
    # both chunk rounds' row-blocks, the paged kernel at 8 x 8 heads
    "kda_step_cell_32": lambda: _kda_case(32, 1),
    "kda_chunk_cell_2x1024": lambda: _kda_case(2, 1024),
    "kda_chunk_half_1x512": lambda: _kda_case(1, 512),
    "grouped_moe_int8_solar_decode_32_b16": lambda: _solar_moe_case(32, 16),
    "grouped_moe_int8_solar_half_1x512_b16": lambda: _solar_moe_case(512, 16),
    "grouped_moe_int8_solar_chunk_1x1024_b32": lambda: _solar_moe_case(1024, 32),
    "grouped_moe_int8_solar_chunk_2x1024_b64": lambda: _solar_moe_case(2048, 64),
    "paged_decode_bf16_solar_32x132": lambda: _solar_paged_case(None, 32),
    "paged_chunk_bf16_solar_tile256x132": lambda: _solar_paged_case(256, 2),
    # granite-4.0-h-micro.longgen-open (PR 53): the state-space recurrence's
    # two kernels at the cell's decode width, its widest prefill and its half;
    # the paged kernel over its lane-padded heads at width 64 and a chunk tile
    "paged_decode_bf16_granite_64x16": lambda: _granite_paged_case(None, 64),
    "paged_chunk_bf16_granite_tile512x16": lambda: _granite_paged_case(512, 4),
    "ssd_step_cell_64": lambda: _ssd_case(64, 1),
    "ssd_chunk_cell_4x512": lambda: _ssd_case(4, 512),
    "ssd_chunk_half_1x256": lambda: _ssd_case(1, 256),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, shapes = KERNEL_CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in shapes]
    # the persistent cache is off in this suite (conftest); a compile for
    # a described device could be written to one but never read back
    assert not jax.config.jax_enable_compilation_cache
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _latent_step(kv, q, latent, index_key, bias, slots, positions, max_pos,
                 *, group_bias: bool):
    """What every latent step program does to the pool, layer after layer:
    the tokens' vectors scattered into their pages (``write_latent_kv``), then
    the latent kernel over the pool; the pool is donated and returned."""
    tables = kv.block_tables[slots]
    total = jnp.zeros((), jnp.float32)
    for layer in range(kv.latent_pages.shape[0]):
        kv = write_latent_kv(kv, layer, latent, index_key, slots, positions)
        out = mla.mla_paged_attention_pallas(
            q, bias, kv.latent_pages, tables, max_pos, layer=layer,
            value_dim=DS_VALUE, group_bias=group_bias)
        total = total + jnp.sum(out.astype(jnp.float32))
        q = q + total.astype(q.dtype)       # the next layer waits for this one
    return kv, total


def _latent_step_case(cell: str, step: str, width: int, spec):
    """(pool shape, arguments of ``_latent_step`` as ``spec(shape, dtype)``,
    group_bias) at a cell's shapes: ``deepseek`` decode steps of 8 rows and
    chunk rounds of 2 x 1024 over [5, 1152, 128, width] with the index pool
    beside it, 128 heads, the 128-page table; ``joyai`` verify steps of 32
    rows x 2 positions x 32 heads and chunk rounds of 2 x 1024 over
    [6, 2304, 128, width], the 64-page table."""
    layers, pages, heads, table, slots, idx = {
        "deepseek": (DS_LAYERS, DS_PAGES, DS_HEADS, DS_TABLE, 8, DS_IDX_DIM),
        "joyai": (6, 2304, 32, 64, 32, None)}[cell]
    bf16, i32 = jnp.bfloat16, jnp.int32
    B, S = {"decode": (8, 1), "verify": (32, 2), "chunk": (2, 1024)}[step]
    tokens = (B,) if step == "decode" else (B, S)
    # decode and verify: a position's heads are the block's rows; a chunk
    # round: some heads x a tile of queries
    q = (B, heads, S, width) if step == "chunk" else (B, S, heads, width)
    tiles = S // mla._ATTN_QUERY_TILE if step == "chunk" else 1
    pool = (layers, pages, PAGE, width)
    kv = LatentKVState(
        spec(pool, bf16), spec((layers, pages, PAGE, idx), bf16) if idx else None,
        spec((slots, table), i32))
    args = [kv, spec(q, bf16), spec((*tokens, width), bf16),
            spec((*tokens, idx), bf16) if idx else None,
            spec((B, S, table * PAGE), jnp.float32), spec((B,), i32),
            spec(tokens, i32), spec((B, tiles), i32)]
    return pool, args, step == "verify"


LATENT_STEP_CASES = [("deepseek", "decode", DS_LATENT),
                     ("deepseek", "chunk", DS_LATENT),
                     ("joyai", "verify", DS_LATENT),
                     ("joyai", "chunk", DS_LATENT),
                     # the fault this guards against: the DECLARED width
                     ("joyai", "verify", DS_DECLARED)]


@pytest.mark.parametrize("cell, step, width", LATENT_STEP_CASES)
def test_latent_step_relayouts_no_pool(v5e, cell, step, width):
    """The latent pool is stored in the layout its writer and its kernel use:
    at the stored width the pool's entry layout, in and out, is major-to-minor
    and the compiled step holds no ``copy`` of the pool's shape. At the
    declared 576 (4.5 lane tiles) the compiler's own parameter layout puts a
    page's tokens in the lanes, and the program copies the WHOLE pool into the
    kernel's layout and back, every step."""
    assert stored_width(DS_DECLARED) == DS_LATENT
    pool, args, group_bias = _latent_step_case(
        cell, step, width, partial(jax.ShapeDtypeStruct, sharding=v5e))
    compiled = jax.jit(partial(_latent_step, group_bias=group_bias),
                       donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    shape = "bf16\\[" + ",".join(map(str, pool)) + "\\]"
    copies = re.findall(rf"= {shape}\{{[^}}]*\}} copy\(", text)
    layouts = [f.latent_pages.layout.major_to_minor for f in (
        compiled.input_formats[0][0], compiled.output_formats[0])]
    assert text.count("tpu_custom_call") >= pool[0]
    if width == DS_LATENT:
        assert not copies and layouts == [(0, 1, 2, 3)] * 2
    else:
        assert len(copies) == 2 and layouts == [(0, 1, 3, 2)] * 2


def test_which_buckets_have_a_half_on_a_v5e_mesh(v5e):
    """On a TPU mesh a bucket's half-length program has to be whole units of
    the family's prefill kernels and keep the bucket's expert formulation
    (``engine._find_half_lengths``): what that gives the cells' buckets."""
    import numpy as np
    from jax.sharding import Mesh

    from mcp_context_forge_tpu.tpu_local.engine import EngineConfig, TPUEngine
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS, family_of

    mesh = Mesh(np.array(list(v5e.device_set)).reshape(1, 1), ("data", "model"))
    sdar_cell = dataclasses.replace(MODEL_CONFIGS["sdar-test"], n_experts=128,
                                    moe_top_k=8, moe_block=32)
    want = {   # config -> (unit, halves of buckets 128, 512, 1024)
        "mistral-7b": (128, {512: 256, 1024: 512}),
        # 256 tokens x top-2 fall under the row-block rule: the scan
        "mixtral-8x7b": (128, {1024: 512}),
        "olmo-hybrid-test": (128, {512: 256, 1024: 512}),
        "deepseek-test": (256, {512: 256, 1024: 512}),
        sdar_cell: (128, {512: 256, 1024: 512}),
    }
    for model, (unit, halves) in want.items():
        config = MODEL_CONFIGS[model] if isinstance(model, str) else model
        engine = TPUEngine.__new__(TPUEngine)
        engine.config = EngineConfig(page_size=128)
        engine.mesh, engine.model_config = mesh, config
        engine._family, engine._kv_dtype = family_of(config), jnp.bfloat16
        engine._prefill_sample_sp = None
        assert engine._family.prefill_unit(mesh, config) == unit, config.name
        found = {}
        for bucket in (128, 512, 1024):   # one bucket an engine, as the cells
            engine.config = EngineConfig(page_size=128,
                                         prefill_buckets=(bucket,))
            found.update(engine._find_half_lengths())
        assert found == halves, config.name


def test_block_step_and_masked_prefill_compile_for_v5e(v5e):
    """The block-diffusion family's two step programs, whole (the while loop
    with the pool in its carry, the paged kernel, the grouped experts over 128
    int8 stacks at the 16-row block a step of 128 tokens takes, the head and
    the confidence sampling; the masked flash prefill with the grouped
    experts at the configuration's block), at the cell's shapes and widths,
    two layers deep: 32 rows x 4 positions over an 8-page bucket, and
    [4, 512]. A lowering Mosaic refuses at the narrow block is found here."""
    from mcp_context_forge_tpu.tpu_local.kv import init_kv_state
    from mcp_context_forge_tpu.tpu_local.models import sdar
    from mcp_context_forge_tpu.tpu_local.models.configs import SdarConfig
    from mcp_context_forge_tpu.tpu_local.models.llama import expert_block
    from mcp_context_forge_tpu.tpu_local.parallel.mesh import make_mesh
    from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree
    from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams

    cfg = SdarConfig(name="sdar-d2", vocab_size=151936, dim=SDAR_D, n_layers=2,
                     n_heads=SDAR_KV * SDAR_G, n_kv_heads=SDAR_KV, head_dim=HD,
                     ffn_hidden=SDAR_F, n_experts=SDAR_E, moe_top_k=8)
    mesh = make_mesh("", devices=[next(iter(v5e.device_set))])
    like = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    params = like(jax.eval_shape(lambda: quantize_tree(
        sdar.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16),
        sdar.params_logical(cfg), scale_dtype=jnp.bfloat16)))
    kv = like(jax.eval_shape(partial(init_kv_state, cfg, 512, PAGE, 32, 8,
                                     dtype=jnp.bfloat16)))
    B, Bl = 32, cfg.block_length
    rows = SamplingParams(spec((B,), jnp.float32), spec((B,), jnp.int32),
                          spec((B,), jnp.float32))
    with mesh:
        step = jax.jit(
            lambda p, kv, tok, pos, m, slots, samp, key: sdar.block_step(
                p, cfg, tok, pos, m, kv, slots, samp, key, ctx_pages=8,
                paged_impl="pallas", mesh=mesh), donate_argnums=(1,))
        text = step.lower(
            params, kv, spec((B, Bl), jnp.int32), spec((B, Bl), jnp.int32),
            spec((B, Bl), jnp.bool_), spec((B,), jnp.int32), rows,
            spec((2,), jnp.uint32)).compile().as_text()
        # the paged kernel and the row-block kernel, a layer each
        assert text.count("tpu_custom_call") >= 2 * cfg.n_layers
        assert " while(" in text and "grouped_moe_q8" in text
        prefill = jax.jit(
            lambda p, kv, tok, pos, slots: sdar.prefill(
                p, cfg, tok, pos, kv, slots, attn_impl="pallas", mesh=mesh,
                head=False)[1], donate_argnums=(1,))
        text = prefill.lower(
            params, kv, spec((4, 512), jnp.int32), spec((4, 512), jnp.int32),
            spec((4,), jnp.int32)).compile().as_text()
        # the masked flash kernel and the grouped experts
        assert text.count("tpu_custom_call") >= cfg.n_layers
        assert "flash_attention" in text and "grouped_moe_q8" in text
    assert sdar.expert_path(cfg, mesh, 4 * 512) == "grouped"
    assert sdar.expert_path(cfg, mesh, B * Bl) == "grouped"
    assert (expert_block(cfg, 4 * 512), expert_block(cfg, B * Bl)) == (128, 16)


def test_window_family_decode_step_compiles_grouped_for_v5e(v5e):
    """trinity-mini-d8.mixedctx-open's decode step, whole, at the cell's
    widths and four layers deep (a dense layer, two window layers and a full
    one over 128 int8 experts top-8): 32 rows over a 32-page bucket lower with
    the row-block kernel in every routed layer, at the 16-row block the step's
    width gives it, and with no loop that carries the expert stacks (the
    expert scan's ``while``; the plan's binary search is a loop too, over a
    vector of 128)."""
    from mcp_context_forge_tpu.tpu_local.models import afmoe
    from mcp_context_forge_tpu.tpu_local.models.configs import AfmoeConfig
    from mcp_context_forge_tpu.tpu_local.parallel.mesh import make_mesh
    from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree

    cfg = AfmoeConfig(name="trinity-d4", vocab_size=200192, dim=2048,
                      n_layers=4, n_heads=32, n_kv_heads=4, head_dim=HD,
                      ffn_hidden=6144, moe_ffn_hidden=1024, n_experts=128,
                      moe_top_k=8, sliding_window=2048, n_dense_layers=1)
    routed = cfg.n_layers - cfg.n_dense_layers
    mesh = make_mesh("", devices=[next(iter(v5e.device_set))])
    like = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    params = like(jax.eval_shape(lambda: quantize_tree(
        afmoe.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16),
        afmoe.params_logical(cfg), scale_dtype=jnp.bfloat16)))
    B = 32
    kv = like(jax.eval_shape(partial(afmoe.init_kv_state, cfg, 4352, PAGE, B,
                                     132, dtype=jnp.bfloat16)))
    assert afmoe.expert_path(cfg, mesh, B) == "grouped"
    assert afmoe.expert_block(cfg, B) == 16

    def lowered(config):
        step = jax.jit(
            lambda p, kv, tok, pos, slots, lens, live: afmoe.decode_step(
                p, config, tok, pos, kv, slots, lens, ctx_pages=32,
                write_mask=live, paged_impl="pallas", mesh=mesh)[:2],
            donate_argnums=(1,))
        with mesh:
            return step.lower(
                params, kv, spec((B,), jnp.int32), spec((B,), jnp.int32),
                spec((B,), jnp.int32), spec((B,), jnp.int32),
                spec((B,), jnp.bool_)).compile().as_text()

    def stack_loops(text):
        return [line for line in text.splitlines()
                if " while(" in line and "s8[128,2048,1024]" in line]

    text = lowered(cfg)
    # the paged kernel in every layer, the row-block kernel in the routed ones
    assert text.count("tpu_custom_call") >= cfg.n_layers + routed
    assert "grouped_moe_q8" in text and not stack_loops(text)
    # what the check tells apart: the same step on the scan
    scan = lowered(dataclasses.replace(cfg, moe_impl="dense"))
    assert "grouped_moe_q8" not in scan and len(stack_loops(scan)) == routed


_TILED = re.compile(r"(\w+)\[([\d,]+)\]\{[\d,]*:T\((\d+),128\)")


def _narrow_tiles(text: str, least: int = 64 * 4096) -> set[tuple[str, tuple, int]]:
    """(dtype, shape, sublanes a tile) of every array of ``least`` elements or
    more that an instruction of the compiled module's ENTRY computation
    yields in tiles of fewer than 8 sublanes (``T(1,128)``, ``T(2,128)``,
    ``T(4,128)``): a vector register is 8 sublanes by 128 lanes, and every
    load of such an array fills that share of it."""
    entry = text[text.index("\nENTRY "):]
    found = set()
    for line in entry.splitlines()[1:]:
        name, eq, rest = line.partition(" = ")
        if not eq:
            continue
        # the result's shape, or the tuple of them: what stands before the
        # operation's name and its operands
        result = rest[:rest.index(") ") + 1] if rest.startswith("(") else \
            rest.split(" ", 1)[0]
        for dtype, dims, sublanes in _TILED.findall(result):
            shape = tuple(int(d) for d in dims.split(","))
            if math.prod(shape) >= least and int(sublanes) < 8:
                found.add((dtype, shape, int(sublanes)))
    return found


def test_granite_decode_step_hands_its_kernel_rows_not_one_token_tiles(v5e):
    """granite-4.0-h-micro.longgen-open's decode step, whole, at the published
    widths and three layers deep (Mamba, attention, Mamba; 64 rows, int8
    weights, the paged kernel): ``ssd_step`` once a Mamba layer, and no array
    of a Mamba layer's token path in tiles of fewer than 8 sublanes. A Mosaic
    call's operand keeps its row-major layout, so a ``[64, 1, 4096]`` operand
    is tiled ``T(1,128)`` and every fusion around the call inherits the tile
    (read on the chip at PR 53: 3.3 ms of a 17.7 ms step, PERF.md section 6,
    PR 54); the counter-example below is such a call. What stays narrow and
    is not a Mamba layer's arithmetic: the convolution tails as the cache
    holds them, ``[rows, taps = 3, 4352]`` (the row gather and the scatter are
    row-major by DMA; one select turns the gathered rows rows-on-sublanes),
    and the paged kernel's ``[64, 8, 4, 128]`` query block of 4 heads a kv
    head."""
    from jax.experimental import pallas as pl

    from mcp_context_forge_tpu.tpu_local.kv import init_kv_state
    from mcp_context_forge_tpu.tpu_local.models import granite_hybrid
    from mcp_context_forge_tpu.tpu_local.models.configs import GraniteHybridConfig
    from mcp_context_forge_tpu.tpu_local.parallel.mesh import make_mesh
    from mcp_context_forge_tpu.tpu_local.quantize import quantize_tree

    cfg = GraniteHybridConfig(
        name="granite-d3", vocab_size=100352, dim=2048, n_layers=3, n_heads=32,
        n_kv_heads=8, head_dim=64, ffn_hidden=8192, mamba_n_heads=64,
        mamba_head_dim=64, mamba_d_state=128,
        layer_types=("mamba", "attention", "mamba"), embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=1 / 64, logits_scaling=8.0)
    mesh = make_mesh("", devices=[next(iter(v5e.device_set))])
    like = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    params = like(jax.eval_shape(lambda: quantize_tree(
        granite_hybrid.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16),
        granite_hybrid.params_logical(cfg), scale_dtype=jnp.bfloat16)))
    B = 64
    kv = like(jax.eval_shape(partial(init_kv_state, cfg, 1025, PAGE, B, 16,
                                     dtype=jnp.bfloat16)))
    step = jax.jit(
        lambda p, kv, tok, pos, slots, lens, live: granite_hybrid.decode_step(
            p, cfg, tok, pos, kv, slots, lens, ctx_pages=16, write_mask=live,
            paged_impl="pallas", mesh=mesh)[:2], donate_argnums=(1,))
    # the described device is not ``jax.devices()``: steer the family's
    # question to the branch the chip takes, here and not in the program
    with mesh, mock.patch.object(granite_hybrid, "on_tpu", lambda mesh: True):
        assert granite_hybrid.delta_impl(mesh, cfg) == "pallas"
        text = step.lower(
            params, kv, spec((B,), jnp.int32), spec((B,), jnp.int32),
            spec((B,), jnp.int32), spec((B,), jnp.int32),
            spec((B,), jnp.bool_)).compile().as_text()
    kernels = re.findall(r"%(\w+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
                         text[text.index("\nENTRY "):])
    assert sorted(kernels) == ["paged_attention", "ssd_step", "ssd_step"]
    narrow = _narrow_tiles(text)
    tails = {n for n in narrow if n[0] == "bf16" and n[1][-2:] == (3, 4352)}
    assert narrow - tails <= {("bf16", (B, 8, 4, 128), 4)}, sorted(narrow - tails)
    assert not any(dtype == "f32" for dtype, _, _ in narrow)

    # the detector fires on what this guards against: a Mosaic call handed a
    # [64, 1, 4096] operand, and the fusion that feeds it
    def one_token_blocks(x):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0
        block = pl.BlockSpec((1, 1, 4096), lambda b: (b, 0, 0))
        return pl.pallas_call(
            kernel, grid=(B,), in_specs=[block], out_specs=block,
            out_shape=jax.ShapeDtypeStruct((B, 1, 4096), jnp.float32),
        )(jnp.tanh(x)[:, None, :])

    text = jax.jit(one_token_blocks).lower(
        spec((B, 4096), jnp.float32)).compile().as_text()
    assert ("f32", (B, 1, 4096), 1) in _narrow_tiles(text)


# ------------------------------------------------- chip_smoke.py, rehearsed

@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke as module
    finally:
        sys.path.remove(REPO)
    return module


def test_chip_smoke_kernel_parity_rehearsal(chip_smoke):
    facts = chip_smoke.phase_kernel_parity("llama3-test", page_size=16,
                                           interpret=True)
    assert set(facts["kernels"]) == {"paged_decode_bf16", "paged_decode_int8",
                                     "paged_chunk_bf16", "flash_prefill"}
    # the kernel alone at the cells' shapes (tiny here: fields, not times)
    assert set(facts["timing"]) == set(chip_smoke.TIMED_SHAPES)
    for timed in facts["timing"].values():
        assert timed["us_per_call"] > 0 and timed["us_per_live_page"] > 0
        assert 0 < timed["live_pages"] <= (timed["grid_steps"]
                                           * timed["block_pages"])


def test_chip_smoke_gateway_rehearsal(chip_smoke, capsys, monkeypatch):
    """The whole one-chip gateway phase — build as ``cli serve`` does, bind a
    socket, chat / stream / burst / embeddings / moderations, every check —
    on the CPU at llama3-test / encoder-tiny."""
    env = {**chip_smoke.GATEWAY_ENV, **chip_smoke.ENGINE_ENV,
           "MCPFORGE_TPU_LOCAL_MODEL": "llama3-test",
           "MCPFORGE_TPU_LOCAL_DTYPE": "float32",
           "MCPFORGE_TPU_LOCAL_EMBEDDING_MODEL": "encoder-tiny",
           "MCPFORGE_TPU_LOCAL_NUM_PAGES": "96"}
    for key in env:     # phase_gateway writes os.environ, as cli serve reads it
        monkeypatch.setenv(key, os.environ.get(key, ""))
    from mcp_context_forge_tpu.config import reset_settings_cache
    try:
        asyncio.run(chip_smoke.phase_gateway(
            env, chip_smoke.CompileMeter(), expect_kernels=False))
    finally:
        monkeypatch.undo()
        reset_settings_cache()
    phases = [json.loads(line)["phase"]
              for line in capsys.readouterr().out.splitlines()
              if line.startswith("{")]
    assert phases == ["build", "health", "chat", "greedy_determinism",
                      "chat_stream", "prefix_primer", "burst", "logits",
                      "embeddings", "moderations", "serving_compiles"]


def test_chip_smoke_four_chip_rehearsal(chip_smoke, capsys):
    """``--chips 4``'s two phases on four of the suite's virtual CPU
    devices: a TP engine against a one-device engine, then one replica per
    device."""
    meter = chip_smoke.CompileMeter()
    devices = jax.devices()[:4]
    asyncio.run(chip_smoke.phase_tp_engine(
        meter, model="llama3-test", expect_kernels=False, devices=devices))
    asyncio.run(chip_smoke.phase_replica_pool(
        meter, model="llama3-test", devices=devices))
    facts = {json.loads(line)["phase"]: json.loads(line)
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")}
    assert set(facts) == {"tp_engine", "tp_vs_one_device", "replica_pool"}
    assert facts["tp_engine"]["mesh"] == {"data": 1, "model": 4}
    assert facts["replica_pool"]["replica_devices"] == [[0], [1], [2], [3]]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the script fails at once — non-zero, and
    ``"ok": false`` on its last line — and never continues on the CPU."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert len(proc.stdout.strip().splitlines()) == 1   # no phase ever ran
