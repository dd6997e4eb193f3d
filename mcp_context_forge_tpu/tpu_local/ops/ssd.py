"""The Mamba-2 state-space recurrence (a selective scan with a scalar decay a
head and ONE group of input and output projections that all heads share).

A head ``h`` keeps ``S_h`` of ``[N, P]`` float32 (``N`` = ``d_state``, ``P`` =
``head_dim``). A token with channels ``x_h`` ``[P]``, step ``dt_h`` (after the
softplus), decay ``a_h = exp(dt_h A_h)`` (``A_h < 0``), and the group's
``B``, ``C`` ``[N]`` does

    S_h <- a_h S_h + B (dt_h x_h)^T,        y_h = S_h^T C + D_h x_h

so a padding token is ``dt = 0`` (``a = 1``, no input). There is no delta
correction, no key or query of a head's own and no normalisation: this is not
``ops/gated_delta.py``'s recurrence with other numbers.

**Layout.** The state pool is ``gated_delta``'s: ``[layers, rows, N, H * P]``
float32, a row's heads side by side on the lane axis (4096 = 32 x 128 lanes
for 64 heads of 64), row 0 the trash row. ``x`` comes and ``y`` leaves as the
state lies, ``[B, S, H * P]``: the mixer's ``x`` is a slice of the
convolution's flat output and its ``y`` is gated and normed flat.

**ssd_step** (decode, one token a row). A grid step holds one row's whole
``[N, H P]`` state in VMEM, decays a head's lanes by its scalar, adds the
rank-one ``B (dt x)^T`` and contracts the sublanes with ``C``, all on the VPU
in float32: ~3 MFLOP against 2 x 2.1 MB at the published widths, bound by
HBM. **What a row brings and takes are 2-D arrays** ``[B, H P]`` (``x``, the
decay and ``dt`` spread over the lanes, ``y``) and ``[B, N]`` (``B``, ``C``),
moved in blocks of ``ROWS`` = 8 rows: the block index is ``b // 8``, so eight
consecutive grid steps name one block, which is fetched and written back once,
and a step picks its row with a dynamic sublane slice (``b % 8``); ``dt x`` is
formed in the kernel. A width that is not whole blocks is padded with idle
steps. **A ``[B, 1, C]`` operand is refused by design**: a Mosaic call's
operands keep their row-major layout, so the one token would be the
second-minor dimension, the compiler would tile the array ``T(1,128)`` (one
sublane of a vector register's eight) and every fusion around the call would
inherit the tile (3.3 ms of a 17.7 ms decode step on the chip: PERF.md
section 6, PR 54). A head's scalar reaches its ``P`` lanes OUTSIDE the kernel,
for all rows at once, through one product with a 0/1 matrix (``_spread``) at
``Precision.HIGHEST``: exact, because a column has one non-zero and the
three bfloat16 pieces of a float32 add back to it; the same product inside a
grid step would push the 1 MB matrix through the MXU once a row. ``B`` and
``C`` arrive as lane rows and reach the sublanes through the diagonal of a
``[N, N]`` select, exactly. **A row on the trash row moves no state**: its
grid step names the state block of the live row before it (the one after it
for the first rows), which is therefore neither fetched nor written back
again, and does nothing but zero its row of the output; with no live row at
all the one trash block is copied through.

**ssd_chunk** (prefill, chunks of ``CHUNK`` = 64 tokens, every exponent <=
0). With ``gamma`` the inclusive cumulative sum of ``dt A`` a head inside the
chunk and ``after_i = gamma_L - gamma_i`` (both summed from terms of one
sign by the caller, never a difference of large numbers):

    G = C B^T                                    [L, L], ONCE for all heads
    Y_h = (tril(exp(gamma_i - gamma_j)) * G)(dt X)_h + e^gamma_h (C S_0)_h
    S_L = e^(gamma_L) S_0 + B^T (e^after dt X)

``C S_0`` and ``B^T (.)`` are each one product over all 4096 lanes; a head's
scalars reach its lanes through one product with a 0/1 matrix (exact: one
non-zero a column). Only the masked ``[L, L]`` factor is a head's own, and
two heads share one ``[L, 2L] x [2L, 128]`` product (their masks side by side
on the lanes, their ``dt X`` block-diagonal below each other), which fills the
MXU's 128 x 128 tile. The difference ``gamma_i - gamma_j`` is formed from the
same numbers on both sides, so the diagonal is exactly 1. Float32 operands at
``Precision.HIGHEST``, float32 accumulation. The grid is (row, chunk): the
row's state stays in VMEM across its chunks and is read and written once a
row a call, in place; a chunk wholly past the row's count names the row's
last real chunk again and costs nothing; a row without a real token is
skipped as in ``ssd_step``. The chunk length is the kernel's own: the
published kernel's 256 (``mamba_chunk_size``) is a blocking of the same sum.
~20 MB of VMEM at the published widths (the state in and out
double-buffered 8.4 MB, the chunk's x and y blocks 4 MB, 7 MB of ``[L, 4096]``
temporaries).

Both calls take the layer index as a prefetched scalar and are jitted on
their own, so a step program traces and lowers each once, whatever its
number of Mamba layers (``gated_delta._chunk_call``: why).

``ssd_recurrence`` is the definition, token by token; ``ssd_chunked`` the
chunkwise form in ``jax.numpy`` (the prefill path off the TPU, and the twin
the chunk kernel is held to).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import _mm     # a float32 product at Precision.HIGHEST

CHUNK = 64                 # tokens a chunk, kernel and twin
ROWS = 8                   # decode rows a block of ssd_step: a tile's sublanes
_VMEM_LIMIT = 48 << 20
_HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ jax.numpy

def ssd_recurrence(x, dt, a_log, b, c, state):
    """The definition, token by token. x: [B, S, H, P]; dt: [B, S, H] (after
    the softplus; 0 on padding); a_log: [H] (``A = -exp(a_log)``); b, c:
    [B, S, N]; state: [B, N, H, P]. Float32 throughout. -> (y [B, S, H, P]
    WITHOUT the skip ``D x``, state)."""
    f32 = lambda v: v.astype(jnp.float32)
    A = -jnp.exp(f32(a_log))

    def step(S, xs):
        xt, dtt, bt, ct = xs                                   # [B, ..]
        S = (S * jnp.exp(dtt * A)[:, None, :, None]
             + jnp.einsum("bn,bhp->bnhp", bt, dtt[..., None] * xt))
        return S, jnp.einsum("bnhp,bn->bhp", S, ct)

    xs = tuple(jnp.moveaxis(f32(v), 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(step, f32(state), xs)
    return jnp.moveaxis(y, 0, 1), state


def chunk_decays(dt, a_log, chunk: int = CHUNK):
    """(gamma, after) [B, S, H] of dt [B, S, H] (S whole chunks): inside each
    chunk the inclusive cumulative sum of ``dt A`` and the sum of the terms
    AFTER a token, each a sum of terms of one sign."""
    B, S, H = dt.shape
    g = (dt.astype(jnp.float32) * -jnp.exp(a_log.astype(jnp.float32))
         ).reshape(B, S // chunk, chunk, H)
    gamma = jnp.cumsum(g, axis=2)
    after = jnp.flip(jnp.cumsum(jnp.flip(g, 2), axis=2), 2) - g
    return gamma.reshape(B, S, H), after.reshape(B, S, H)


def ssd_chunked(x, dt, a_log, b, c, state, chunk: int = CHUNK):
    """The same function a chunk at a time (module docstring, "ssd_chunk").
    Arguments and result as :func:`ssd_recurrence`; S is padded to whole
    chunks with identity steps (``dt = 0``)."""
    B, S, H, P = x.shape
    pad = -S % chunk
    f32 = lambda v: v.astype(jnp.float32)
    x, dt, b, c = f32(x), f32(dt), f32(b), f32(c)
    if pad:
        zeros = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = zeros(x), zeros(dt), zeros(b), zeros(c)
    n = (S + pad) // chunk
    gamma, after = chunk_decays(dt, a_log, chunk)
    split = lambda v: jnp.moveaxis(v.reshape(B, n, chunk, *v.shape[2:]), 1, 0)
    xc, dtc, bc, cc, gc, ac = (split(v) for v in (x, dt, b, c, gamma, after))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(S0, xs):
        x_, dt_, b_, c_, gam, aft = xs          # [B, L, ..]
        dtx = dt_[..., None] * x_                                # [B, L, H, P]
        G = jnp.einsum("bin,bjn->bij", c_, b_, precision=_HI)
        diff = gam[:, :, None, :] - gam[:, None, :, :]           # [B, i, j, H]
        M = jnp.exp(jnp.where(lower[None, :, :, None], diff, -jnp.inf)) \
            * G[..., None]
        y = (jnp.einsum("bijh,bjhp->bihp", M, dtx, precision=_HI)
             + jnp.exp(gam)[..., None]
             * jnp.einsum("bin,bnhp->bihp", c_, S0, precision=_HI))
        S1 = (jnp.exp(gam[:, -1])[:, None, :, None] * S0
              + jnp.einsum("bjn,bjhp->bnhp", b_,
                           jnp.exp(aft)[..., None] * dtx, precision=_HI))
        return S1, y

    state, y = jax.lax.scan(step, f32(state), (xc, dtc, bc, cc, gc, ac))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S + pad, H, P)
    return y[:, :S], state


def ssd_reference(x, dt, a_log, b, c, pool, rows, fresh, *, layer: int,
                  chunked: bool = True):
    """The kernels' twin through the pool: x [B, S, H * P] as the state lies;
    rows [B] int32 (0 = trash), fresh [B] bool (start from zero). -> (y
    [B, S, H * P] float32 without the skip, pool)."""
    B, S, HP = x.shape
    H = dt.shape[-1]
    state = jnp.where(fresh[:, None, None], 0.0, pool[layer, rows])
    state = state.reshape(B, -1, H, HP // H)
    form = ssd_chunked if chunked and S > 1 else ssd_recurrence
    y, state = form(x.reshape(B, S, H, HP // H), dt, a_log, b, c, state)
    return (y.reshape(B, S, HP),
            pool.at[layer, rows].set(state.reshape(B, -1, HP).astype(pool.dtype)))


# --------------------------------------------------------------------- Pallas

def takes(n_heads: int, head_dim: int, d_state: int) -> bool:
    """Whether the kernels take this geometry: two heads fill a 128-lane tile
    (``head_dim`` 64), an even number of them, the state's rows whole sublane
    tiles."""
    return head_dim == CHUNK and n_heads % 2 == 0 and d_state % 8 == 0


def live_rows(rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(the state row each grid step NAMES, live [B] int32) from rows [B]
    (0: the trash row). A live step names its own row; an idle one the row of
    the live step before it, or of the first live step where none is before
    it, so that its block is the one already there; all idle: row 0."""
    at = jnp.arange(rows.shape[0], dtype=jnp.int32)
    live = rows > 0
    before = jax.lax.cummax(jnp.where(live, at, -1))
    named = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    return rows[named].astype(jnp.int32), live.astype(jnp.int32)


def _spread(n_heads: int, head_dim: int) -> jax.Array:
    """[H, H P] 0/1: a product with it (``_mm``) puts a head's scalar on each
    of the head's lanes, exactly: one non-zero a column."""
    lanes = jnp.arange(n_heads * head_dim)[None, :] // head_dim
    return (lanes == jnp.arange(n_heads)[:, None]).astype(jnp.float32)


def _column(row, n: int):
    """A lane row [1, n] as a sublane column [n, 1], exactly: the diagonal of
    a select."""
    iota = jax.lax.broadcasted_iota
    eye = iota(jnp.int32, (n, n), 0) == iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _step_kernel(named_ref, live_ref, fresh_ref, layer_ref, x_ref, decay_ref,
                 dt_ref, b_ref, c_ref, s_in_ref, y_ref, s_out_ref):
    """One decode row. x, decay, dt, y: the [ROWS, H P] block the row lies in;
    b, c: [ROWS, N]; the row is sublane ``b % ROWS`` of each."""
    del named_ref, layer_ref                        # ride the index maps
    b = pl.program_id(0)
    n = s_in_ref.shape[0]
    row = pl.ds(b % ROWS, 1)

    @pl.when(live_ref[b] > 0)
    def _():
        decay = decay_ref[row, :]                                # [1, H P]
        dtx = dt_ref[row, :] * x_ref[row, :]
        b_col = _column(b_ref[row, :], n)
        c_col = _column(c_ref[row, :], n)
        S = jnp.where(fresh_ref[b] > 0, 0.0, s_in_ref[...]) * decay + b_col * dtx
        s_out_ref[...] = S
        y_ref[row, :] = jnp.sum(S * c_col, axis=0, keepdims=True)

    @pl.when(live_ref[b] <= 0)
    def _():
        y_ref[row, :] = jnp.zeros((1, y_ref.shape[1]), y_ref.dtype)

    @pl.when((live_ref[b] <= 0) & (b == 0))
    def _():
        # the block stays until a live step fills it, or (no live row at all)
        # goes back as it came
        s_out_ref[...] = s_in_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(x, over, b, c, pool, named, live, fresh, layer, *,
               interpret: bool):
    """x [B, H P], b, c [B, N] float32, B whole blocks of ``ROWS``; over
    [2 B, H P]: the rows' decay, then their dt, spread over the lanes (one
    array read through two block maps)."""
    B, HP = x.shape
    N = b.shape[-1]
    row_spec = pl.BlockSpec((None, None, N, HP),
                            lambda b, named, live, fresh, layer:
                            (layer[0], named[b], 0, 0))
    # eight consecutive grid steps name one block: fetched, and written back,
    # once
    block = lambda b, *_: (b // ROWS, 0)
    lanes, group = pl.BlockSpec((ROWS, HP), block), pl.BlockSpec((ROWS, N), block)
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[lanes, lanes,
                      pl.BlockSpec((ROWS, HP),
                                   lambda b, *_: (B // ROWS + b // ROWS, 0)),
                      group, group, row_spec],
            out_specs=[lanes, row_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, HP), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 9 of the call (four prefetched scalars, x, over twice, b,
        # c) is the pool: updated in place
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssd_step",
        interpret=interpret,
    )(named, live, fresh, layer, x, over, over, b, c, pool)


def _chunk_kernel(named_ref, live_ref, count_ref, fresh_ref, layer_ref, x_ref,
                  scal_ref, pairs_ref, bc_ref, spread_ref, s_in_ref, y_ref,
                  s_out_ref, dtx_ref):
    """One chunk of ``CHUNK`` tokens of ALL heads of one row. x [L, H P];
    scal [3, L, H]: dt, gamma, after; pairs [H / 2, 2 L]: a pair of heads'
    gamma as lane rows, side by side; bc [2, L, N]; spread [H, H P] 0/1."""
    del named_ref, layer_ref                        # ride the index maps
    b, ci = pl.program_id(0), pl.program_id(1)
    f32, L = jnp.float32, CHUNK
    H = scal_ref.shape[3]
    iota = jax.lax.broadcasted_iota
    live = live_ref[b] > 0

    @pl.when(live & (ci == 0))
    def _():
        s_out_ref[...] = jnp.where(fresh_ref[b] > 0, 0.0, s_in_ref[...])

    @pl.when((live_ref[b] <= 0) & (b == 0) & (ci == 0))
    def _():
        s_out_ref[...] = s_in_ref[...]              # _step_kernel: why

    real = count_ref[b] - ci * L                    # the chunk's real tokens

    @pl.when(jnp.logical_not(live) | (real <= 0))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live & (real > 0))
    def _():
        x = x_ref[0].astype(f32)                                 # [L, H P]
        dt, gam, aft = scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2]
        B_, C_ = bc_ref[0, 0].astype(f32), bc_ref[0, 1].astype(f32)   # [L, N]
        # a head's scalars over its lanes: one product with the 0/1 matrix
        over = _mm(jnp.concatenate([dt, jnp.exp(gam), jnp.exp(aft)], axis=0),
                   spread_ref[...])                              # [3 L, H P]
        dtx = x * over[:L]
        dtx_ref[...] = dtx
        S0 = s_out_ref[...]
        y_ref[0] = _mm(C_, S0) * over[L:2 * L]
        s_out_ref[...] = (over[2 * L - 1:2 * L] * S0
                          + _mm(B_, dtx * over[2 * L:], ((0,), (0,))))
        # the chunk's own tokens: G once, a pair of heads a product
        G = _mm(C_, jnp.concatenate([B_, B_], axis=0), ((1,), (1,)))  # [L, 2 L]
        row = iota(jnp.int32, (L, 2 * L), 0)
        col = iota(jnp.int32, (L, 2 * L), 1)
        second = col >= L
        causal = jnp.where(second, col - L, col) <= row
        head_lane = iota(jnp.int32, (L, H), 1)
        below = iota(jnp.int32, (2 * L, 2 * L), 0) >= L
        right = iota(jnp.int32, (2 * L, 2 * L), 1) >= L

        def pair(p, carry):
            lanes = pl.ds(pl.multiple_of(p * 2 * L, 2 * L), 2 * L)
            column = lambda h: jnp.sum(jnp.where(head_lane == h, gam, 0.0),
                                       axis=1, keepdims=True)    # [L, 1]
            mine = jnp.where(second, column(2 * p + 1), column(2 * p))
            mask = jnp.exp(jnp.where(causal, mine - pairs_ref[0, pl.ds(p, 1), :],
                                     -jnp.inf)) * G
            both = jnp.concatenate([dtx_ref[:, lanes]] * 2, axis=0)   # [2 L, 2 L]
            y_ref[0, :, lanes] += _mm(mask, jnp.where(below == right, both, 0.0))
            return carry

        jax.lax.fori_loop(0, H // 2, pair, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_call(x, scal, pairs, bc, spread, pool, named, live, counts, fresh,
                layer, *, interpret: bool):
    B, S, HP = x.shape
    H, N = scal.shape[-1], bc.shape[-1]
    L = CHUNK
    # a chunk wholly past the row's count names the row's last real chunk
    # again: a block is not fetched twice, so such a chunk moves nothing in
    at = lambda b, c, counts: jnp.minimum(c, jnp.maximum(counts[b] - 1, 0) // L)
    row_spec = pl.BlockSpec((None, None, N, HP),
                            lambda b, c, named, live, counts, fresh, layer:
                            (layer[0], named[b], 0, 0))
    return pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, S // L),
            in_specs=[
                pl.BlockSpec((1, L, HP), lambda b, c, named, live, counts, *_:
                             (b, at(b, c, counts), 0)),
                pl.BlockSpec((1, 3, L, H), lambda b, c, named, live, counts, *_:
                             (b, 0, at(b, c, counts), 0)),
                pl.BlockSpec((1, H // 2, 2 * L),
                             lambda b, c, named, live, counts, *_:
                             (b * (S // L) + at(b, c, counts), 0, 0)),
                pl.BlockSpec((1, 2, L, N), lambda b, c, named, live, counts, *_:
                             (b, 0, at(b, c, counts), 0)),
                pl.BlockSpec((H, HP), lambda b, c, *_: (0, 0)),
                row_spec],
            out_specs=[pl.BlockSpec((1, L, HP), lambda b, c, *_: (b, c, 0)),
                       row_spec],
            scratch_shapes=[pltpu.VMEM((L, HP), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, HP), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 10 of the call (five prefetched scalars, x, scal, pairs, bc,
        # spread) is the pool: updated in place
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssd_chunk",
        interpret=interpret,
    )(named, live, counts, fresh, layer, x, scal, pairs, bc, spread, pool)


def ssd_pallas(x, dt, a_log, b, c, pool, rows, counts, fresh, *, layer: int,
               interpret: bool = False):
    """x: [B, S, H * P] as the state lies; dt: [B, S, H] float32 (0 on
    padding); a_log: [H]; b, c: [B, S, N]; pool [layers, rows, N, H * P]
    float32; rows, counts (a row's real tokens, a prefix of its S), fresh:
    [B]. -> (y [B, S, H * P] float32 WITHOUT the skip, pool). The kernel is
    ``ssd_step`` where S == 1 and ``ssd_chunk`` elsewhere (S whole chunks)."""
    B, S, HP = x.shape
    H, N = dt.shape[-1], b.shape[-1]
    P = HP // H
    if not takes(H, P, N):
        raise ValueError(f"{H} heads of {P} over a state of {N} rows: the "
                         f"kernels take an even number of heads of {CHUNK}")
    f32 = jnp.float32
    layer_no = jnp.full((1,), layer, jnp.int32)
    rows = jnp.where(counts > 0, rows, 0)
    dt = dt.astype(f32)
    if S == 1:
        # a width that is not whole blocks gets idle steps up to one (trash
        # row, nothing moved: live_rows)
        wide = lambda v: jnp.pad(v, [(0, -B % ROWS)] + [(0, 0)] * (v.ndim - 1))
        named, live = live_rows(wide(rows))
        decay = jnp.exp(dt[:, 0] * -jnp.exp(a_log.astype(f32)))
        over = _mm(jnp.concatenate([wide(decay), wide(dt[:, 0])], axis=0),
                   _spread(H, P))                                # [2 B, H P]
        tok = lambda v: wide(v[:, 0].astype(f32))
        y, pool = _step_call(tok(x), over, tok(b), tok(c), pool, named, live,
                             wide(fresh.astype(jnp.int32)), layer_no,
                             interpret=interpret)
        return y[:B, None], pool
    named, live = live_rows(rows)
    fresh = fresh.astype(jnp.int32)
    if S % CHUNK:
        raise ValueError(f"S={S} must be whole chunks of {CHUNK}")
    gamma, after = chunk_decays(dt, a_log)
    scal = jnp.stack([dt, gamma, after], axis=1)                 # [B, 3, S, H]
    # a pair of heads' gamma as lane rows: [B * chunks, H / 2, 2 L]
    pairs = gamma.reshape(B * (S // CHUNK), CHUNK, H // 2, 2).transpose(0, 2, 3, 1)
    pairs = pairs.reshape(B * (S // CHUNK), H // 2, 2 * CHUNK)
    return _chunk_call(x, scal, pairs, jnp.stack([b, c], axis=1), _spread(H, P),
                       pool, named, live, counts.astype(jnp.int32), fresh,
                       layer_no, interpret=interpret)
