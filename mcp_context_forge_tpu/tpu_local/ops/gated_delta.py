"""The gated delta rule (linear attention with a fixed-size state a sequence).

A head keeps ``S`` of ``[d_k, d_v]`` float32. A token with key ``k``, query
``q`` (both already normalised and scaled), value ``v``, decay ``alpha =
exp(g)`` and write strength ``beta`` does

    S <- alpha S + beta k (v - alpha S^T k)^T,        o = S^T q

so a padding token is the identity step ``alpha = 1, beta = 0``.

**Two forms of the decay**, told apart by the SHAPE of ``g`` and traced apart
(a static specialisation: neither form's program carries anything of the
other's). ``g`` of ``[B, S, H]`` is one decay a head (the gated delta rule as
published). ``g`` of ``[B, S, H, d_k]`` is a decay a head AND a key channel,
``S <- Diag(alpha) S`` (Kimi Delta Attention, arXiv:2510.26692): row ``i`` of
a head's state decays by ``alpha_i``, and the rest of the step is the same.

**Layout.** The state pool is ``[layers, rows, d_k, H * d_v]``: a row's heads
lie side by side on the lane axis (5760 = 45 x 128 lanes for 30 heads of 192),
where ``[.., H, d_k, d_v]`` would pad every head's 192 lanes to 256 in HBM and
move a third more bytes on every step. Row 0 is the trash row, as page 0 is the
trash page: idle decode rows and a batch's padding rows read and write it.

**Kernels.** ``gated_delta_chunk`` (prefill, chunk rounds) and
``gated_delta_step`` (decode) are ONE Pallas body: a grid step holds one row's
whole state in VMEM (the output block, resident across the row's token tiles)
and walks the row's REAL tokens one by one on the VPU in float32, all heads at
once; tokens past the row's length cost nothing. A token's per-head vectors
arrive as one ``[2 d_k + 8, H]`` tile (``k^T``, ``q^T``, ``alpha``, ``beta``):
a head's key is then a sublane column that broadcasts along that head's lanes,
so ``S^T k`` is a multiply and a sublane reduction, and the rank-1 update an
outer product of a column and a row. The channel form's tile is ``[3 d_k + 8,
H]`` (``alpha^T`` a column like ``k^T``), its decay one more spread column in
place of a broadcast row, and its kernels are named ``kda_chunk`` /
``kda_step`` in a trace, so that what reads ``gated_delta_*`` there reads the
scalar form alone. It walks ``_CHANNEL_TOKEN_TILE`` = 32 tokens a grid step:
a 4.2 MB state of 64 x 128 x 128 in and out, double-buffered, beside 64
tokens of 392 rows (their 64 lanes padded to 128) asks for 48.5 MB of scoped
VMEM against ``_VMEM_LIMIT`` = 48, which the chip's compiler refuses inside a
step program although a compile of the kernel alone for a DESCRIBED v5e lets
it pass (PERF.md section 6, PR 50); 32 tokens ask for 34 MB. Heads whose
``d_v`` is not a multiple of 128 are taken ``G`` at a time (2 for 192) so
that every slice of the state is lane-aligned. The state row is found through
scalar-prefetched row ids and updated in place (``input_output_aliases``); a
row that starts a sequence (``fresh``) starts from zero without reading what
its last tenant left.

The chunked WY form on the MXU is ``gated_delta_chunked`` (``jax.numpy``): the
scalar form's path off the TPU, and the twin its kernel is held to.
``gated_delta_recurrence`` is the token-by-token definition of both forms, and
the channel form's path off the TPU: a WY form that factors the decay out of
the chunk (``q * e^gamma``, ``k * e^-gamma``) overflows float32 inside one
64-token chunk where a channel's ``g`` reaches -10 a token, the safe one forms
``exp(gamma_i - gamma_j)`` a channel under the mask (a ``[C, C, d_k]``
intermediate a head), and a twin that nothing times is not worth a second
derivation to hold: the Pallas body is token-sequential and has no such term.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64                 # tokens a WY chunk (jnp path)
_TOKEN_TILE = 64           # tokens a grid step of the kernel holds
_CHANNEL_TOKEN_TILE = 32   # ... of the channel form (module docstring)
_VMEM_LIMIT = 48 << 20


# ------------------------------------------------------------------ jax.numpy

def gated_delta_recurrence(q, k, v, g, beta, state):
    """The definition, token by token. q, k: [B, S, H, dk]; v: [B, S, H, dv];
    beta: [B, S, H]; g: [B, S, H], or [B, S, H, dk] for a decay a key channel;
    state: [B, H, dk, dv]. Float32 throughout. -> (o [B, S, H, dv], state)."""
    f32 = lambda a: a.astype(jnp.float32)
    channel = g.ndim == 4

    def step(S, xs):
        qt, kt, vt, gt, bt = xs                      # [B, H, ..]
        S = S * (jnp.exp(gt)[..., None] if channel
                 else jnp.exp(gt)[..., None, None])
        err = vt - jnp.einsum("bhkv,bhk->bhv", S, kt)
        S = S + jnp.einsum("bhk,bhv->bhkv", kt, err * bt[..., None])
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(f32(a), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, f32(state), xs)
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The same function in chunks of ``chunk`` tokens (the WY form): inside a
    chunk the ``chunk`` rank-1 updates collapse to ``T = (I + A)^-1
    diag(beta)`` with ``A = strict_lower(diag(beta) (K K^T * Gamma))``, and the
    state moves a chunk at a time. Arguments and result as
    :func:`gated_delta_recurrence` with one decay a head; S is padded to a
    multiple of ``chunk`` with identity steps."""
    B, S, H, dk = q.shape
    if g.ndim != 3:
        raise ValueError("the chunked form takes one decay a head: a decay a "
                         "channel runs the recurrence (module docstring)")
    pad = -S % chunk
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = (f32(a) for a in (q, k, v, g, beta))
    if pad:
        zeros = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = (zeros(a) for a in (q, k, v, g, beta))
    n = (S + pad) // chunk
    # [n, B, H, C, ..]
    split = lambda a: jnp.moveaxis(
        a.reshape(B, n, chunk, H, *a.shape[3:]), (1, 3), (0, 2))
    qc, kc, vc = split(q), split(k), split(v)
    gc, bc = split(g), split(beta)                               # [n, B, H, C]
    gamma = jnp.cumsum(gc, axis=-1)
    diff = gamma[..., :, None] - gamma[..., None, :]             # gamma_i - gamma_j
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    Gamma = jnp.exp(jnp.where(lower, diff, -jnp.inf))            # 0 above the diagonal
    hi = jax.lax.Precision.HIGHEST
    kk = jnp.einsum("nbhik,nbhjk->nbhij", kc, kc, precision=hi) * Gamma
    A = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1),
                  bc[..., :, None] * kk, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    rhs = jnp.concatenate([kc * jnp.exp(gamma)[..., None], vc], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        eye + A, bc[..., None] * rhs, lower=True, unit_diagonal=True)
    W, U = solved[..., :dk], solved[..., dk:]
    qk = jnp.einsum("nbhik,nbhjk->nbhij", qc, kc, precision=hi) * Gamma
    q_in = qc * jnp.exp(gamma)[..., None]
    total = gamma[..., -1]                                        # [n, B, H]
    k_out = kc * jnp.exp(total[..., None] - gamma)[..., None]

    def step(S0, xs):
        W_, U_, qk_, q_in_, k_out_, total_ = xs
        v_new = U_ - jnp.einsum("bhck,bhkv->bhcv", W_, S0, precision=hi)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_in_, S0, precision=hi)
             + jnp.einsum("bhij,bhjv->bhiv", qk_, v_new, precision=hi))
        S1 = (S0 * jnp.exp(total_)[..., None, None]
              + jnp.einsum("bhck,bhcv->bhkv", k_out_, v_new, precision=hi))
        return S1, o

    state, o = jax.lax.scan(step, f32(state), (W, U, qk, q_in, k_out, total))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, S + pad, H, -1)
    return o[:, :S], state


def pool_rows(pool: jax.Array, layer: int, rows: jax.Array, n_heads: int
              ) -> jax.Array:
    """Rows of one layer of the pool as [B, H, dk, dv]."""
    flat = pool[layer, rows]                                     # [B, dk, H * dv]
    B, dk, hv = flat.shape
    return flat.reshape(B, dk, n_heads, hv // n_heads).transpose(0, 2, 1, 3)


def flat_rows(state: jax.Array) -> jax.Array:
    """[B, H, dk, dv] -> the pool's [B, dk, H * dv]."""
    B, H, dk, dv = state.shape
    return state.transpose(0, 2, 1, 3).reshape(B, dk, H * dv)


def gated_delta_reference(q, k, v, g, beta, pool, rows, fresh, *, layer: int,
                          chunked: bool = True):
    """The kernels' twin through the pool: rows [B] int32 (0 = trash), fresh
    [B] bool (start from zero). -> (o [B, S, H, dv] float32, pool)."""
    H = q.shape[2]
    state = jnp.where(fresh[:, None, None, None], 0.0,
                      pool_rows(pool, layer, rows, H))
    if q.shape[1] == 1 or not chunked or g.ndim == 4:
        o, state = gated_delta_recurrence(q, k, v, g, beta, state)
    else:
        o, state = gated_delta_chunked(q, k, v, g, beta, state)
    return o, pool.at[layer, rows].set(flat_rows(state).astype(pool.dtype))


# --------------------------------------------------------------------- Pallas

def head_group(n_heads: int, dv: int) -> int | None:
    """Heads taken together so that their lanes are whole 128-lane tiles, or
    None where no such grouping divides the heads (the jnp path then)."""
    group = 128 // math.gcd(dv, 128)
    return group if n_heads % group == 0 else None


def _kernel(rows_ref, count_ref, fresh_ref, tile_ref, v_ref, s_in_ref,
            o_ref, s_out_ref, *, dk: int, dv: int, n_heads: int, group: int,
            tokens: int, channel: bool):
    del rows_ref                                    # rides the index maps
    b, ti = pl.program_id(0), pl.program_id(1)

    @pl.when(ti == 0)
    def _():
        s_out_ref[...] = jnp.where(fresh_ref[b] > 0, 0.0, s_in_ref[...])

    o_ref[...] = jnp.zeros_like(o_ref)
    width = group * dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def spread(tile, row0, rows, head):
        """[rows, width]: column ``head + i`` of the tile's rows over the
        lanes of the group's i-th head."""
        out = jnp.broadcast_to(tile[row0:row0 + rows, head:head + 1],
                               (rows, width))
        for i in range(1, group):
            col = jnp.broadcast_to(
                tile[row0:row0 + rows, head + i:head + i + 1], (rows, width))
            out = jnp.where(lane >= i * dv, col, out)
        return out

    decay_rows = dk if channel else 1
    whole_rows = width == 128 and tokens % 8 == 0
    if whole_rows:
        sublane = jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)

    def token(t, carry):
        tile = tile_ref[0, t]                        # [(2 or 3) dk + 8, H]
        v_row = v_ref[0, pl.ds(t, 1), :]                        # [1, H * dv]
        for p in range(n_heads // group):
            lanes = slice(p * width, (p + 1) * width)
            head = p * group
            k_col = spread(tile, 0, dk, head)
            q_col = spread(tile, dk, dk, head)
            # one row all of a head's lanes share, or a column like k_col
            alpha = spread(tile, 2 * dk, decay_rows, head)
            beta = spread(tile, 2 * dk + decay_rows, 1, head)
            S = s_out_ref[:, lanes] * alpha
            err = v_row[:, lanes] - jnp.sum(S * k_col, axis=0, keepdims=True)
            S = S + k_col * (err * beta)
            s_out_ref[:, lanes] = S
            out = jnp.sum(S * q_col, axis=0, keepdims=True)
            if whole_rows:
                # Mosaic stores no single [1, 128] row at a dynamic sublane
                # ("dynamic store with unaligned indices"; wider rows it
                # does): rewrite the aligned 8 rows that hold token t
                base = pl.multiple_of(t // 8 * 8, 8)
                o_ref[0, pl.ds(base, 8), lanes] = jnp.where(
                    sublane == t % 8, out, o_ref[0, pl.ds(base, 8), lanes])
            else:
                o_ref[0, pl.ds(t, 1), lanes] = out
        return carry

    real = jnp.clip(count_ref[b] - ti * tokens, 0, tokens)
    jax.lax.fori_loop(0, real, token, 0)


def pack_token_tiles(q, k, g, beta):
    """q, k: [B, S, H, dk]; g, beta: [B, S, H] -> [B, S, 2 dk + 8, H] float32:
    rows ``k^T``, ``q^T``, ``exp(g)``, ``beta``, six rows of zeros. With g
    [B, S, H, dk], [B, S, 3 dk + 8, H]: ``k^T``, ``q^T``, ``exp(g)^T``,
    ``beta``, seven rows of zeros."""
    f32 = lambda a: a.astype(jnp.float32)
    B, S, H, _ = q.shape
    if g.ndim == 4:
        gates = jnp.concatenate([jnp.swapaxes(jnp.exp(f32(g)), 2, 3),
                                 f32(beta)[:, :, None]], axis=2)
    else:
        gates = jnp.stack([jnp.exp(f32(g)), f32(beta)], axis=2)  # [B, S, 2, H]
    return jnp.concatenate(
        [jnp.swapaxes(f32(k), 2, 3), jnp.swapaxes(f32(q), 2, 3), gates,
         jnp.zeros((B, S, -gates.shape[2] % 8, H), jnp.float32)], axis=2)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def gated_delta_pallas(q, k, v, g, beta, pool, rows, counts, fresh, *,
                       layer: int, interpret: bool = False):
    """q, k: [B, S, H, dk]; v: [B, S, H, dv]; beta: [B, S, H]; g: [B, S, H],
    or [B, S, H, dk] for a decay a key channel; pool [L, R, dk, H * dv]
    float32; rows, counts (a row's real tokens, a prefix of its S), fresh: [B]
    int32. -> (o [B, S, H, dv] float32, pool). The kernel is
    ``gated_delta_step`` where S == 1 and ``gated_delta_chunk`` elsewhere
    (``kda_step`` / ``kda_chunk`` with a decay a channel)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    channel = g.ndim == 4
    group = head_group(H, dv)
    if group is None:
        raise ValueError(f"{H} heads of d_v={dv} have no lane-aligned grouping")
    tokens = min(S, _CHANNEL_TOKEN_TILE if channel else _TOKEN_TILE)
    if S % tokens:
        raise ValueError(f"S={S} must be a multiple of {tokens}")
    tiles = pack_token_tiles(q, k, g, beta)
    values = v.astype(jnp.float32).reshape(B, S, H * dv)
    row_map = lambda b, t, rows, *_: (layer, rows[b], 0, 0)
    tok_map = lambda b, t, *_: (b, t, 0)
    o, pool = pl.pallas_call(
        functools.partial(_kernel, dk=dk, dv=dv, n_heads=H, group=group,
                          tokens=tokens, channel=channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, S // tokens),
            in_specs=[
                pl.BlockSpec((1, tokens, tiles.shape[2], H),
                             lambda b, t, *_: (b, t, 0, 0)),
                pl.BlockSpec((1, tokens, H * dv), tok_map),
                pl.BlockSpec((None, None, dk, H * dv), row_map),
            ],
            out_specs=[
                pl.BlockSpec((1, tokens, H * dv), tok_map),
                pl.BlockSpec((None, None, dk, H * dv), row_map),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 of the call (after the three prefetched scalars and the
        # two token inputs) is the pool: updated in place
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=(("kda" if channel else "gated_delta")
              + ("_step" if S == 1 else "_chunk")),
        interpret=interpret,
    )(rows.astype(jnp.int32), counts.astype(jnp.int32),
      fresh.astype(jnp.int32), tiles, values, pool)
    return o.reshape(B, S, H, dv), pool
