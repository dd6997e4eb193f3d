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

**Kernels: the token walk.** ``gated_delta_step`` (decode) and the
``gated_delta_chunk`` of a bucket that is not whole chunks are ONE Pallas
body: a grid step holds one row's whole state in VMEM (the output block,
resident across the row's token tiles) and walks the row's REAL tokens one
by one on the VPU in float32, all heads at once; tokens past the row's length
cost nothing. A token's per-head
vectors arrive as one ``[2 d_k + 8, H]`` tile (``k^T``, ``q^T``, ``alpha``,
``beta``): a head's key is then a sublane column that broadcasts along that
head's lanes, so ``S^T k`` is a multiply and a sublane reduction, and the
rank-1 update an outer product of a column and a row. The channel form's tile
is ``[3 d_k + 8, H]`` (``alpha^T`` a column like ``k^T``), its decay one more
spread column in place of a broadcast row, and its kernels are named
``kda_chunk`` / ``kda_step`` in a trace, so that what reads ``gated_delta_*``
there reads the scalar form alone. Heads whose ``d_v`` is not a multiple of
128 are taken ``G`` at a time (2 for 192) so that every slice of the state is
lane-aligned. The state row is found through scalar-prefetched row ids and
updated in place (``input_output_aliases``); a row that starts a sequence
(``fresh``) starts from zero without reading what its last tenant left.

**Which shapes take which body** (a rule of ``S, H, d_k, d_v`` a form,
beside :func:`head_group`; no setting). A decode step walks in both forms
(``gated_delta_step`` / ``kda_step``), and so does a bucket that is not whole
64-token chunks. Whole chunks of the SCALAR form take the chunkwise body
(``gated_delta_chunk`` still, PR 52) wherever the heads have a lane-aligned
grouping and the keys fill whole sublane tiles (:func:`scalar_chunk_group`:
Olmo's 30 heads of 96 x 192, two a 384-lane slab; the test preset's 4 of 16 x
32, all four a slab). Whole chunks of the CHANNEL form take theirs where
``d_k`` and ``d_v`` are multiples of 128 (:func:`chunk_heads`); other heads
walk, ``_CHANNEL_TOKEN_TILE`` = 32 tokens a grid step (64 tokens of 392 rows
beside the whole 4.2 MB state of 64 x 128 x 128, double-buffered, ask for
48.5 MB of scoped VMEM against ``_VMEM_LIMIT`` = 48, which the chip's
compiler refuses inside a step program: PERF.md section 6, PR 50).
:func:`chunk_body` names the answer, and the engine counts its prefill
dispatches by it (``EngineStats.delta_chunkwise_steps`` / ``.delta_walk_steps``).

**The chunkwise channel body** (PR 51). For a chunk of ``C`` = 64 tokens of
one head with entry state ``S_0`` and cumulative log-decay ``gamma_t = sum_{s
<= t} g_s`` (a ``[d_k]`` vector a token, non-increasing) the recurrence is

    A_ij = beta_i sum_c k_ic k_jc e^(gamma_ic - gamma_jc)   (j < i, else 0)
    P_ij =        sum_c q_ic k_jc e^(gamma_ic - gamma_jc)   (j <= i, else 0)
    (I + A) V_new = diag(beta) (V - (K * e^gamma) S_0)
    O = (Q * e^gamma) S_0 + P V_new
    S_C = Diag(e^gamma_C) S_0 + (K * e^(gamma_C - gamma))^T V_new

(the WY form's ``W`` and ``U`` are not formed: one kernel does a chunk whole,
so the system is solved once, for ``V_new``). Every exponent above is ``<= 0``
except in a FACTORED form of the pairwise terms: ``q * e^gamma`` against ``k *
e^-gamma`` overflows float32 inside one chunk once a channel's ``g`` reaches
-1.4 a token (the model's draws reach -30), so they are formed as the
published chunkwise KDA algorithm forms them (arXiv:2510.26692 and its open
kernels), in sub-blocks of ``_SUB`` = 16 tokens. A query sub-block ``a``
against an EARLIER key sub-block ``s`` takes as reference ``r`` the ``gamma``
of the last token of ``s``: ``e^(gamma_i - gamma_j) = e^(gamma_i - r) e^(r -
gamma_j)`` with both exponents ``<= 0``, and an underflow is a true zero; the
keys are rescaled once, the queries (and the keys in their role as rows of
``A``) once a pair, and all six pairs are ONE product of ``[192, d_k]`` by
``[d_k, 64]``. Inside a sub-block ``e^(gamma_i - gamma_j)`` is formed a
channel, a key column ``j`` at a time (at most 16 tokens x ``d_k``
exponentials and two lane reductions a column), and the column is used at
once: ``(I + A)`` is unit lower triangular and is solved by column-oriented
forward substitution (column ``j`` of ``A`` times the finished row ``j``
leaves the rows below, and column ``j`` of ``P`` times it joins the outputs;
earlier sub-blocks leave and join by one product of ``[P; A]`` rows), never by
a product of powers of ``A``, whose entries reach 2 with ``beta`` up to 2 and
repeated keys. All products take float32 operands at the MXU's full float32
precision (``Precision.HIGHEST``) and accumulate in float32; the cumulative
sum is six shifted adds down the sublanes. ``e^gamma_C`` reaches the state's
rows through the one transpose that ``(K * e^(gamma_C - gamma))^T`` needs.

The grid is (row, block of up to 8 heads, chunk): a block's ``[d_k, 8 d_v]``
state stays in VMEM across the row's chunks and is read and written once a
row a call, in place. q, k, g, v are read AS THE MIXER MADE THEM, ``[B, S, H,
d]`` in blocks of ``[64, 8, d]``: a head's ``[64, d]`` is one row a token, 8
rows apart (a strided load), and o is written a row a token too, so a prefill
program builds no token tile and relays out nothing (``[B, S, H * d]`` is NOT
a view of ``[B, S, H, d]`` on the TPU: that reshape alone moved every operand
once more and cost as much as the products). beta comes as ``[64, H]``. The
heads of a block are worked on ``_ABREAST`` = 4 at a time, stage by stage:
their chains of small products are independent, and in program order one
head's waits are the next one's work (one head at a time takes 1.8 x as
long, PERF.md section 6). A chunk wholly past the row's count costs nothing,
a partly filled one runs whole with its tokens past the count made identity
steps (``g = 0, beta = 0``). About 5 MB of VMEM whatever the number of heads.
The layer index is a prefetched scalar, not a static one, and the call is
jitted on its own: the body is traced once a shape and lowered once a step
program, which calls it once a KDA layer (the walk is lowered once a layer).

**The chunkwise scalar body** (PR 52). With one decay a head ``gamma_t`` is a
scalar a token and the pairwise factor a ``[C, C]`` mask a head:

    Gamma_ij = exp(gamma_i - gamma_j)   (j <= i, else 0; every exponent <= 0)
    A = strict_lower(diag(beta) (K K^T * Gamma)),   P = lower(Q K^T * Gamma)
    (I + A) V_new = diag(beta) (V - (K * e^gamma) S_0)
    O = (Q * e^gamma) S_0 + P V_new
    S_C = e^gamma_C S_0 + (K * e^(gamma_C - gamma))^T V_new

(``gated_delta_chunked`` with one solve, for ``V_new``). ``Gamma`` is formed
as the masked difference, never factored (Olmo's ``g`` sums below -88, the
end of float32's ``exp``, inside one chunk); ``gamma`` and ``gamma_C - gamma``
are each a masked lane reduction of the chunk's ``g`` (sums of terms of one
sign, no difference of large numbers), and a token's scalar reaches the lanes
from the sublanes through the diagonal of a ``[C, C]`` select, exactly.
``(I + A)`` is solved by forward substitution in 16-row blocks: earlier
blocks leave by one product, inside a block column ``j`` times the finished
row ``j`` leaves the rows below; ``Q K^T`` and ``K K^T`` are one product,
``K e^gamma`` and ``Q e^gamma`` against ``S_0`` another, and a slab's heads
update their states in ONE product (their tokens one below the other, each
head's ``V_new`` on its own lanes), so the slab is written as it lies in the
pool. Float32 operands at ``Precision.HIGHEST``, float32 accumulation.

The geometry is Olmo's: 30 heads of ``d_k`` = 96, ``d_v`` = 192. The grid is
(row, chunk) and a grid step holds ALL heads of a chunk: q and k come AS THE
MIXER MADE THEM in ``[64, H, d_k]`` blocks, g and beta as ``[64, H]``; v
comes and o leaves AS THE STATE LIES, ``[64, H d_v]`` with a slab's heads
side by side on the lanes (the mixer's v is a slice of the convolution's flat
output and its o is normed and gated flat, so neither is relaid out for the
kernel's sake: ``[64, H, d_v]`` blocks measured 0.8 ms a prefill more in the
copies around the kernel and nothing less inside it), and the row's whole
``[d_k, H d_v]`` state stays in VMEM across its chunks (21 MB of
``_VMEM_LIMIT`` = 48 at Olmo's widths before Mosaic's own temporaries: 1 MB a
q or k block padded to 32 x 128 a token, 1.5 MB a v or o block, 2.2 MB the
state in and out, all double-buffered, and 2 MB of heads-first copies).
Mosaic's strided load, which the channel body reads a head with, wants a last
dimension of exactly 128 (refused at 96, 192 and 256), so a grid step first
turns its q and k blocks heads-first into scratch (``swapaxes``) and a head
is then one plain load (``ref[0, :, i, :]`` a head: 8 % slower). The heads
are worked on in slabs of :func:`head_group` heads (lane-aligned in the
pool), ``_SCALAR_ABREAST`` = 10 heads in step a turn of the loop (4: 12 %
slower, all 30: no faster and three times the Mosaic compile), the slabs a
turn does not divide in a last, shorter turn. A chunk wholly past the row's
count names the row's last real chunk in its index maps, so nothing is
fetched for it, and writes zeros; a partly filled one runs whole with its
tokens past the count made identity steps. The layer index is a prefetched
scalar and the call jitted on its own, as the channel body's.

The chunked WY form on the MXU in ``jax.numpy`` is ``gated_delta_chunked``:
the scalar form's path off the TPU, and the twin its kernels are held to.
``gated_delta_recurrence`` is the token-by-token definition of both forms, the
channel form's path off the TPU and the twin all chunkwise bodies are held to.
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


# ------------------------------------------- the channel form's chunk, on the MXU

_SUB = 16                  # tokens a sub-block of a chunk (module docstring)
_ABREAST = 4               # heads of a grid step worked on in step


def chunk_heads(seq: int, n_heads: int, dk: int, dv: int) -> int | None:
    """Heads a grid step of the chunkwise channel body holds, or None where a
    call of this shape takes the token walk: the body wants whole chunks and
    every head's keys and values on whole 128-lane tiles."""
    if seq % CHUNK or dk % 128 or dv % 128:
        return None
    return math.gcd(n_heads, 8)


def _mm(a, b, contract=((1,), (0,))):
    """A float32 product at the MXU's full float32 precision."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _chunk_of_heads(heads):
    """A chunk of a few heads, on values and in step: heads is a list of (q, k
    [C, dk]; gam [C, dk] the inclusive cumulative log-decay; v [C, dv]; beta
    [C, 1]; state [dk, dv] on entry) -> a list of (o [C, dv], the state on
    leaving). Module docstring, "the chunkwise channel body". The heads'
    chains of products are independent; written stage by stage over all of
    them, one head's waits are the next one's work."""
    f32, C, c = jnp.float32, CHUNK, _SUB
    subs = C // c
    block = lambda a, n: a[n * c:(n + 1) * c]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) // c   # key sub-block
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    pairs = [(a, s) for a in range(1, subs) for s in range(a)]
    through, rhs, cross = [], [], []
    for q, k, gam, v, beta, state in heads:
        decay = jnp.exp(gam)
        through.append(_mm(jnp.concatenate([k * decay, q * decay], axis=0), state))
        rhs.append(beta * (v - through[-1][:C]))     # (I + A) V_new = rhs
        # every (query sub-block a, key sub-block s < a) in ONE product: both
        # sides around gamma at the END of s, both exponents <= 0
        ends = [gam[(s + 1) * c - 1:(s + 1) * c] for s in range(subs - 1)]
        keys = jnp.concatenate(
            [block(k, s) * jnp.exp(ends[s] - block(gam, s))
             for s in range(subs - 1)] + [jnp.zeros((c, q.shape[1]), f32)], axis=0)
        scaled = []
        for a, s in pairs:
            into = jnp.exp(block(gam, a) - ends[s])
            scaled += [block(q, a) * into, block(k, a) * into]
        cross.append(_mm(jnp.concatenate(scaled, axis=0), keys, ((1,), (1,))))
    solved, outs = [[] for _ in heads], [[] for _ in heads]
    for a in range(subs):
        xs, os_ = [block(r, a) for r in rhs], [block(t, subs + a) for t in through]
        if a:
            for h, (_, _, _, _, beta, _) in enumerate(heads):
                # rows [P; A] of this sub-block against all earlier ones
                before = jnp.zeros((2 * c, C), f32)
                for n, (a_, s) in enumerate(pairs):
                    if a_ == a:
                        before = jnp.where(
                            lane == s, cross[h][2 * c * n:2 * c * (n + 1)], before)
                moved = _mm(jnp.concatenate(
                    [before[:c], block(beta, a) * before[c:]], axis=0)[:, :a * c],
                    jnp.concatenate(solved[h], axis=0))
                xs[h], os_[h] = xs[h] - moved[c:], os_[h] + moved[:c]
        # inside the sub-block: exp(gamma_i - gamma_j) a channel, a key
        # column j at a time; the column leaves the rows below it at once
        # (forward substitution) and row j, finished, joins the outputs
        here = [(block(q, a), block(k, a), block(gam, a), block(beta, a))
                for q, k, gam, _, beta, _ in heads]
        for j in range(c):
            top = c // 2 if j >= c // 2 else 0            # rows above: masked
            for h, (qa, ka, ga, ba) in enumerate(here):
                w = ka[j:j + 1] * jnp.exp(ga[top:] - ga[j:j + 1])
                col_a = jnp.sum(ka[top:] * w, axis=1, keepdims=True)
                col_p = jnp.sum(qa[top:] * w, axis=1, keepdims=True)
                if top:
                    zeros = jnp.zeros((top, 1), f32)
                    col_a = jnp.concatenate([zeros, col_a], axis=0)
                    col_p = jnp.concatenate([zeros, col_p], axis=0)
                x = xs[h]
                x = x - jnp.where(row > j, col_a * ba, 0.0) * x[j:j + 1]
                os_[h] = os_[h] + jnp.where(row >= j, col_p, 0.0) * x[j:j + 1]
                xs[h] = x
        for h in range(len(heads)):
            solved[h].append(xs[h])
            outs[h].append(os_[h])
    done = []
    for h, (q, k, gam, _, _, state) in enumerate(heads):
        # K * e^(gamma_C - gamma) with e^gamma_C in the rows below it,
        # transposed: the chunk's decay lands down the state's rows
        last = gam[C - 1:C]
        out = jnp.concatenate([k * jnp.exp(last - gam), jnp.broadcast_to(
            jnp.exp(last), gam.shape)], axis=0).T
        done.append((jnp.concatenate(outs[h], axis=0),
                     out[:, C:C + 1] * state
                     + _mm(out[:, :C], jnp.concatenate(solved[h], axis=0))))
    return done


def _chunk_kernel(rows_ref, count_ref, fresh_ref, layer_ref, q_ref, k_ref,
                  g_ref, v_ref, beta_ref, s_in_ref, o_ref, s_out_ref, *,
                  dv: int, heads: int):
    """One chunk of ``CHUNK`` tokens of ``heads`` heads of one row,
    ``_ABREAST`` heads at a time (:func:`_chunk_of_heads`: why)."""
    del rows_ref, layer_ref                         # ride the index maps
    b, hb, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32, C = jnp.float32, CHUNK
    side = math.gcd(heads, _ABREAST)

    @pl.when(ci == 0)
    def _():
        s_out_ref[...] = jnp.where(fresh_ref[b] > 0, 0.0, s_in_ref[...])

    real = count_ref[b] - ci * C                    # the chunk's real tokens

    @pl.when(real <= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real > 0)
    def _():
        iota = jax.lax.broadcasted_iota
        token = iota(jnp.int32, (C, 1), 0)
        valid = token < real
        head_lane = iota(jnp.int32, (C, beta_ref.shape[2]), 1)
        betas = beta_ref[0].astype(f32)                       # [C, H]

        def of_head(ref, i):
            """Head i's [C, d] of a [1, C, heads, d] block: a row a token,
            ``heads`` rows apart, as ONE strided load (``ref[0, :, i, :]``
            loads a sublane at a time and doubles the kernel's time; as a
            store it costs 2 %, and the interpreter has no store through a
            reshaped ref)."""
            return ref.reshape(C * heads, ref.shape[3])[
                pl.ds(i, C, stride=heads), :]

        def load(i):
            lv = pl.ds(pl.multiple_of(i * dv, 128), dv)
            # a token past the row's count is the identity step
            gam = jnp.where(valid, of_head(g_ref, i).astype(f32), 0.0)
            shift = 1
            while shift < C:                                  # inclusive cumsum
                gam = gam + jnp.where(token >= shift,
                                      pltpu.roll(gam, shift, 0), 0.0)
                shift *= 2
            beta = jnp.where(valid, jnp.sum(
                jnp.where(head_lane == hb * heads + i, betas, 0.0), axis=1,
                keepdims=True), 0.0)                          # [C, 1]
            return (of_head(q_ref, i).astype(f32), of_head(k_ref, i).astype(f32),
                    gam, of_head(v_ref, i).astype(f32), beta, s_out_ref[:, lv])

        def some_heads(n, carry):
            each = [n * side + i for i in range(side)]
            done = _chunk_of_heads([load(i) for i in each])
            for i, (o, state) in zip(each, done):
                o_ref[0, :, i, :] = o
                s_out_ref[:, pl.ds(pl.multiple_of(i * dv, 128), dv)] = state
            return carry

        jax.lax.fori_loop(0, heads // side, some_heads, 0)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _chunk_call(q, k, v, g, beta, pool, rows, counts, fresh, layer, *,
                heads: int, interpret: bool):
    """The chunkwise body over a bucket. ``layer`` is an int32 [1] ARRAY (a
    prefetched scalar like the row ids, not a static index as the walk's):
    jitted on its own, the body is traced once a shape and lowered once a
    step program, which calls it once a KDA layer."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    tok = lambda width: pl.BlockSpec((1, CHUNK, heads, width),
                                     lambda b, h, c, *_: (b, c, h, 0))
    row_spec = pl.BlockSpec((None, None, dk, heads * dv),
                            lambda b, h, c, rows, counts, fresh, layer:
                            (layer[0], rows[b], 0, h))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, dv=dv, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, H // heads, S // CHUNK),
            in_specs=[tok(dk), tok(dk), tok(dk), tok(dv),
                      pl.BlockSpec((1, CHUNK, H), lambda b, h, c, *_: (b, c, 0)),
                      row_spec],
            out_specs=[tok(dv), row_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 9 of the call (four prefetched scalars, q, k, g, v, beta)
        # is the pool: updated in place
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="kda_chunk",
        interpret=interpret,
    )(rows.astype(jnp.int32), counts.astype(jnp.int32),
      fresh.astype(jnp.int32), layer, q, k, g, v, beta, pool)


# -------------------------------------------- the scalar form's chunk, on the MXU

_SCALAR_ABREAST = 10       # heads of a grid step worked on in step


def scalar_chunk_group(seq: int, n_heads: int, dk: int, dv: int) -> int | None:
    """Heads a lane-aligned slab of the state holds (:func:`head_group`) where
    a call of the scalar form of this shape takes the chunkwise body, or None
    where it takes the token walk: the body wants whole chunks, keys on whole
    sublane tiles (they are the state's rows) and a grouping of the heads."""
    if seq % CHUNK or dk % 8:
        return None
    return head_group(n_heads, dv)


def chunk_body(seq: int, n_heads: int, dk: int, dv: int, channel: bool) -> str:
    """``chunkwise`` or ``walk``: the body a call of ``seq`` tokens of this
    head geometry traces, by its form's rule of shape."""
    rule = chunk_heads if channel else scalar_chunk_group
    return "chunkwise" if rule(seq, n_heads, dk, dv) else "walk"


def _scalar_chunk_of_heads(heads, slabs):
    """A chunk of a few heads of the scalar form, on values and in step: heads
    is a list of (q, k [C, dk]; g [C, 1] the log-decay a token; v [C, dv];
    beta [C, 1]), slabs the states on entry of every ``group`` heads in turn,
    side by side on the lanes [dk, group * dv] -> (a list of o [C, dv], the
    slabs on leaving). Module docstring, "the chunkwise scalar body"."""
    f32, C, c = jnp.float32, CHUNK, _SUB
    group = len(heads) // len(slabs)
    dv = slabs[0].shape[1] // group
    iota = jax.lax.broadcasted_iota
    row, col = iota(jnp.int32, (C, C), 0), iota(jnp.int32, (C, C), 1)
    block = lambda a, n: a[n * c:(n + 1) * c]
    down = lambda column: jnp.sum(                   # [C, 1] -> [1, C], exactly
        jnp.where(row == col, column, 0.0), axis=0, keepdims=True)
    through, rhs, lower_p, lower_a, keys, totals = [], [], [], [], [], []
    for h, (q, k, g, v, beta) in enumerate(heads):
        state = slabs[h // group][:, h % group * dv:(h % group + 1) * dv]
        # gamma_i, the sum of the g up to token i, and gamma_C - gamma_i, the
        # sum of those after it: each a lane reduction a token, no difference
        g_row = down(g)
        gam = jnp.sum(jnp.where(col <= row, g_row, 0.0), axis=1, keepdims=True)
        after = jnp.sum(jnp.where(col > row, g_row, 0.0), axis=1, keepdims=True)
        keys.append(k * jnp.exp(after))
        totals.append(jnp.exp(jnp.sum(g, axis=0, keepdims=True)))     # [1, 1]
        decay = jnp.exp(gam)
        through.append(_mm(jnp.concatenate([k * decay, q * decay], axis=0), state))
        rhs.append(beta * (v - through[-1][:C]))     # (I + A) V_new = rhs
        # exp(gamma_i - gamma_j) as the masked difference: every exponent <= 0
        pair = jnp.exp(jnp.where(col <= row, gam - down(gam), -jnp.inf))
        both = _mm(jnp.concatenate([q, k], axis=0), k, ((1,), (1,)))
        lower_p.append(both[:C] * pair)
        lower_a.append(jnp.where(col < row, beta * both[C:] * pair, 0.0))
    # (I + A) is unit lower triangular: forward substitution, a 16-row block
    # at a time; earlier blocks leave by a product, inside a block column j
    # times the finished row j leaves the rows below it
    solved = [[] for _ in heads]
    for a in range(C // c):
        xs = [block(r, a) for r in rhs]
        if a:
            xs = [x - _mm(block(A, a)[:, :a * c], jnp.concatenate(done, axis=0))
                  for x, A, done in zip(xs, lower_a, solved)]
        here = [block(A, a)[:, a * c:(a + 1) * c] for A in lower_a]
        for j in range(c - 1):
            xs = [x - A[:, j:j + 1] * x[j:j + 1] for x, A in zip(xs, here)]
        for done, x in zip(solved, xs):
            done.append(x)
    v_new = [jnp.concatenate(rows, axis=0) for rows in solved]
    outs = [t[C:] + _mm(P, x) for t, P, x in zip(through, lower_p, v_new)]
    # the states: K * e^(gamma_C - gamma) against V_new, a slab's heads in ONE
    # product: their tokens one below the other, each head's V_new on its own
    # lanes of the slab, which is so written as it lies in the pool
    lanes = iota(jnp.int32, (1, group * dv), 1) // dv
    zeros = lambda n: [jnp.zeros((C, n * dv), f32)] if n else []
    left = []
    for s, slab in enumerate(slabs):
        mine = range(s * group, (s + 1) * group)
        values = [jnp.concatenate(zeros(i) + [v_new[h]] + zeros(group - 1 - i),
                                  axis=1) for i, h in enumerate(mine)]
        decay = jnp.zeros((1, group * dv), f32)
        for i, h in enumerate(mine):
            decay = jnp.where(lanes == i, totals[h], decay)
        left.append(decay * slab + _mm(
            jnp.concatenate([keys[h] for h in mine], axis=0),
            jnp.concatenate(values, axis=0), ((0,), (0,))))
    return outs, left


def _scalar_chunk_kernel(rows_ref, count_ref, fresh_ref, layer_ref, q_ref,
                         k_ref, v_ref, g_ref, beta_ref, s_in_ref, o_ref,
                         s_out_ref, q_heads, k_heads, *, dv: int, group: int):
    """One chunk of ``CHUNK`` tokens of ALL heads of one row, the slabs of
    ``group`` heads ``_SCALAR_ABREAST`` heads at a time
    (:func:`_scalar_chunk_of_heads`)."""
    del rows_ref, layer_ref                         # ride the index maps
    b, ci = pl.program_id(0), pl.program_id(1)
    f32, C = jnp.float32, CHUNK
    H = beta_ref.shape[2]
    slabs, width = H // group, group * dv
    abreast = max(1, _SCALAR_ABREAST // group)      # slabs a turn

    @pl.when(ci == 0)
    def _():
        s_out_ref[...] = jnp.where(fresh_ref[b] > 0, 0.0, s_in_ref[...])

    real = count_ref[b] - ci * C                    # the chunk's real tokens

    @pl.when(real <= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real > 0)
    def _():
        iota = jax.lax.broadcasted_iota
        valid = iota(jnp.int32, (C, 1), 0) < real
        head_lane = iota(jnp.int32, (C, H), 1)
        # a token past the row's count is the identity step
        gs = jnp.where(valid, g_ref[0].astype(f32), 0.0)          # [C, H]
        betas = jnp.where(valid, beta_ref[0].astype(f32), 0.0)
        # a head's [C, dk] is a row a token, H rows apart: the strided load
        # that reads it wants a last dimension of exactly 128 (Mosaic), so
        # the blocks are turned heads-first once and a head is one plain load
        for ref, turned in ((q_ref, q_heads), (k_ref, k_heads)):
            turned[...] = jnp.swapaxes(ref[0].astype(f32), 0, 1)

        def column(table, i):
            return jnp.sum(jnp.where(head_lane == i, table, 0.0), axis=1,
                           keepdims=True)                         # [C, 1]

        def some_slabs(first, n):
            lanes = [pl.ds(pl.multiple_of((first + s) * width, 128), width)
                     for s in range(n)]
            each = [(first + s) * group + i for s in range(n) for i in range(group)]
            # v and o lie as the state does, a slab's heads side by side
            values = [v_ref[0, :, at].astype(f32) for at in lanes]
            outs, left = _scalar_chunk_of_heads(
                [(q_heads[i], k_heads[i], column(gs, i),
                  values[m // group][:, m % group * dv:(m % group + 1) * dv],
                  column(betas, i)) for m, i in enumerate(each)],
                [s_out_ref[:, at] for at in lanes])
            for s, (at, slab) in enumerate(zip(lanes, left)):
                o_ref[0, :, at] = jnp.concatenate(
                    outs[s * group:(s + 1) * group], axis=1)
                s_out_ref[:, at] = slab

        def turn(n, carry):
            some_slabs(n * abreast, abreast)
            return carry

        jax.lax.fori_loop(0, slabs // abreast, turn, 0)
        if slabs % abreast:
            some_slabs(slabs - slabs % abreast, slabs % abreast)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _scalar_chunk_call(q, k, v, g, beta, pool, rows, counts, fresh, layer, *,
                       group: int, interpret: bool):
    """The chunkwise body of the scalar form over a bucket; ``layer`` an
    int32 [1] array and the call jitted on its own, as :func:`_chunk_call`."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    # a chunk wholly past the row's count names the row's last real chunk
    # again: a block is not fetched twice, so such a chunk moves nothing in
    live = lambda b, c, counts: jnp.minimum(
        c, jnp.maximum(counts[b] - 1, 0) // CHUNK)
    keys = pl.BlockSpec((1, CHUNK, H, dk), lambda b, c, rows, counts, *_:
                        (b, live(b, c, counts), 0, 0))
    flat = lambda width: pl.BlockSpec(
        (1, CHUNK, width),
        lambda b, c, rows, counts, *_: (b, live(b, c, counts), 0))
    row_spec = pl.BlockSpec((None, None, dk, H * dv),
                            lambda b, c, rows, counts, fresh, layer:
                            (layer[0], rows[b], 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_scalar_chunk_kernel, dv=dv, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, S // CHUNK),
            in_specs=[keys, keys, flat(H * dv), flat(H), flat(H), row_spec],
            out_specs=[pl.BlockSpec((1, CHUNK, H * dv),
                                    lambda b, c, *_: (b, c, 0)), row_spec],
            scratch_shapes=[pltpu.VMEM((H, CHUNK, dk), jnp.float32)] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 9 of the call (four prefetched scalars, q, k, v, g, beta)
        # is the pool: updated in place
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gated_delta_chunk",
        interpret=interpret,
    )(rows.astype(jnp.int32), counts.astype(jnp.int32),
      fresh.astype(jnp.int32), layer, q, k, v.reshape(B, S, H * dv), g, beta,
      pool)
    return o.reshape(B, S, H, dv), pool


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
    heads = chunk_heads(S, H, dk, dv) if channel else None
    if heads:
        return _chunk_call(q, k, v, g, beta, pool, rows, counts, fresh,
                           jnp.full((1,), layer, jnp.int32), heads=heads,
                           interpret=interpret)
    if not channel and scalar_chunk_group(S, H, dk, dv):
        return _scalar_chunk_call(q, k, v, g, beta, pool, rows, counts, fresh,
                                  jnp.full((1,), layer, jnp.int32),
                                  group=group, interpret=interpret)
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
