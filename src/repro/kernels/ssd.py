"""Fused chunked SSD scan (Mamba-2, arXiv:2405.21060): forward and backward.

``ssd_chunk_scan`` computes what :func:`repro.models.mamba2._ssd_chunked`
computes (its oracle, :func:`repro.kernels.ref.ref_ssd_chunk_scan`) from the
layer's own tensors, with the running state and every per-chunk intermediate
kept in VMEM:

    x  (B, L, H*P) bf16   the conv's output, heads side by side
    dt (B, L, H)   f32    after softplus and ``dt_bias``
    A  (H,)        f32    ``-exp(A_log)``
    Bm, Cm (B, L, G*N) bf16, one (N,) projection per GROUP (no repeat)
    -> y (B, L, H*P) f32

Grid ``(batch, chunk, head block)``.  A head block is ``tiles`` 128-lane
tiles of ``x`` (``128 // P`` heads a tile), all in one group.  The chunk axis
is sequential and carries the block's ``(heads * P, N)`` f32 state in a VMEM
scratch; the head blocks of one chunk run back to back, so ``C Bᵀ`` (Q, Q)
is computed once per group and chunk, masked to its lower triangle, and
reused by every head of the group.  Per head and chunk:

    cs   = cumsum(A dt)                       (f32, log-step shifts)
    L    = exp(segsum(cs)) on and below the diagonal
    y    = ((C Bᵀ) ∘ L)(x dt) + exp(cs) ∘ (C stateᵀ)
    state <- exp(cs[-1]) state + (exp(cs[-1] - cs) ∘ x dt)ᵀ B

The (Q, Q) work runs on 128 x 128 tiles, skipping those above the diagonal.
Everything else works on whole 128-lane tiles (two P=64 heads side by side):
a product that belongs to one head of a tile masks the other head's lanes.

Matrix products take bf16 operands with f32 accumulation (one MXU pass, as
XLA's DEFAULT precision does for the oracle's f32 einsums on TPU); the f32
operand of each product (x dt and its decayed form, the decay-weighted
scores, the state, the cotangents) is rounded to bf16 only at that product.
Segment sums, exponentials and every reduction are f32.

The forward saves each chunk's incoming state ``(B, C, H*P, N)`` f32 as the
only residual beside the inputs.  The backward kernel runs the chunks in
reverse carrying ``d state`` in VMEM, recomputes the chunk's forward, and
returns dx, d dt, dA and, summed over each group's heads inside the kernel,
dB and dC per group.  No per-head ``(Q, Q)`` buffer or head-repeated B/C
reaches HBM in either direction.

:func:`supports` is the shape predicate: head dim 64 or 128, state a
multiple of 128 lanes, chunk 128 or 256 dividing the sequence, and whole
128-lane tiles in each group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["supports", "ssd_chunk_scan", "ssd_chunk_scan_fwd", "ssd_chunk_scan_bwd"]

LANES = 128
TILE = 128            # rows of a (Q, Q) tile
_VMEM_LIMIT = 64 * 1024 * 1024
_MAX_TILES = 4        # 128-lane tiles of x per program


def supports(*, seq: int, chunk: int, n_heads: int, head_dim: int,
             d_state: int, n_groups: int) -> bool:
    """True where the kernel tiles the shapes (module docstring)."""
    if head_dim not in (64, 128) or d_state % LANES or chunk not in (128, 256):
        return False
    if seq % chunk or n_heads % n_groups:
        return False
    return (n_heads // n_groups) % (LANES // head_dim) == 0


def _tiles(n_heads: int, head_dim: int, n_groups: int) -> int:
    """128-lane tiles per program: the most, up to ``_MAX_TILES``, that keep
    a program inside one group."""
    per_group = (n_heads // n_groups) * head_dim // LANES
    return max(t for t in range(1, _MAX_TILES + 1) if per_group % t == 0)


# -- in-kernel helpers -------------------------------------------------------

def _mm(a, b):        # a @ b
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_nt(a, b):     # a @ b.T
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_tn(a, b):     # a.T @ b
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bf(v):
    return v.astype(jnp.bfloat16)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _scan_lanes(v, *, reverse: bool = False):
    """Inclusive prefix (``reverse``: suffix) sum along the last axis, by
    log-step shifts of the whole row."""
    q = v.shape[-1]
    ax = v.ndim - 1
    idx = _iota(v.shape, ax)
    s = 1
    while s < q:
        if reverse:      # v[i] += v[i + s]
            v = v + jnp.where(idx < q - s, pltpu.roll(v, q - s, ax), 0.0)
        else:            # v[i] += v[i - s]
            v = v + jnp.where(idx >= s, pltpu.roll(v, s, ax), 0.0)
        s *= 2
    return v


def _lane_pick(row, k):
    """``row[:, k]`` of a (1, m) row as a (1, 1) array."""
    return jnp.sum(jnp.where(_iota(row.shape, 1) == k, row, 0.0), axis=1,
                   keepdims=True)


def _masked_cb(cmat, bmat):
    """C Bᵀ (Q, Q) f32, zero above the diagonal."""
    cb = _mm_nt(cmat, bmat)
    return jnp.where(_iota(cb.shape, 0) >= _iota(cb.shape, 1), cb, 0.0)


class _Block:
    """One program's per-chunk scalars for its ``heads`` heads: the
    log-decay prefix sums ``cs`` as rows (lanes = position) and columns
    (sublanes = position), dt as columns, and lane-expanded forms for one
    128-lane tile of x, where head ``k`` owns lanes ``[kP, (k+1)P)``."""

    def __init__(self, dt_rows, a_blk, p, cs_scr):
        self.q = q = dt_rows.shape[-1]
        self.p, self.per_tile = p, LANES // p
        self.cs_rows = _scan_lanes(dt_rows * a_blk)          # (heads, Q)
        cs_scr[...] = self.cs_rows       # read back by lane tile in ltile
        self.cs_scr = cs_scr
        self.cs_cols = self.cs_rows.T                        # (Q, heads)
        self.dt_cols = dt_rows.T
        self.cs_last = [_lane_pick(self.cs_rows[k:k + 1, :], q - 1)
                        for k in range(dt_rows.shape[0])]    # (1, 1) each

    def head_of_lane(self, rows):
        return _iota((rows, LANES), 1) // self.p

    def own(self, v, i):
        """``v`` (R, 128) with every lane but head ``i``'s zeroed."""
        if self.per_tile == 1:
            return v
        return jnp.where(self.head_of_lane(v.shape[0]) == i, v, 0.0)

    def expand(self, cols, t):
        """(R, heads) per-head columns -> (R, 128) lanes of tile ``t``."""
        k0 = t * self.per_tile
        out = jnp.broadcast_to(cols[:, k0:k0 + 1], (cols.shape[0], LANES))
        for i in range(1, self.per_tile):
            out = jnp.where(self.head_of_lane(cols.shape[0]) == i,
                            cols[:, k0 + i:k0 + i + 1], out)
        return out

    def lane_row(self, vals, t):
        """Per-head (1, 1) values -> (1, 128) lanes of tile ``t``."""
        k0 = t * self.per_tile
        out = jnp.broadcast_to(vals[k0], (1, LANES))
        for i in range(1, self.per_tile):
            out = jnp.where(self.head_of_lane(1) == i, vals[k0 + i], out)
        return out

    def decays(self, t):
        """(dt, exp(cs), exp(cs[-1] - cs)) of tile ``t``, each (Q, 128)."""
        cs = self.expand(self.cs_cols, t)
        return (self.expand(self.dt_cols, t), jnp.exp(cs),
                jnp.exp(self.lane_row(self.cs_last, t) - cs))

    def col(self, k):
        """Head ``k``'s cs broadcast along lanes, (Q, 128), for ``ltile``."""
        return jnp.broadcast_to(self.cs_cols[:, k:k + 1], (self.q, LANES))

    def ltile(self, col, k, ti, tj):
        """exp(segsum) of head ``k`` (``col`` = ``self.col(k)``) on (row tile
        ti, column tile tj), tj <= ti; above the diagonal it is 1 (the masked
        C Bᵀ zeroes it)."""
        seg = col[_rows(ti)] - self.cs_scr[k:k + 1, _rows(tj)]
        if ti == tj:
            seg = jnp.minimum(seg, 0.0)
        return jnp.exp(seg)

    def pairs(self):
        n = self.q // TILE
        return [(ti, tj) for ti in range(n) for tj in range(ti + 1)]


def _rows(i):
    return slice(i * TILE, (i + 1) * TILE)


def _lanes(t):
    return slice(t * LANES, (t + 1) * LANES)


# -- forward -----------------------------------------------------------------

def _fwd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, *rest, p, tiles,
                blocks_per_group, save_states):
    if save_states:
        st_ref, state_scr, cb_scr, cs_scr = rest
    else:
        st_ref, (state_scr, cb_scr, cs_scr) = None, rest
    c, j = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state_scr[j] = jnp.zeros(state_scr.shape[1:], jnp.float32)

    @pl.when(j % blocks_per_group == 0)
    def _():
        cb_scr[...] = _masked_cb(c_ref[0], b_ref[0])

    blk = _Block(dt_ref[0, 0], a_ref[0], p, cs_scr)
    cmat, bmat = c_ref[0], b_ref[0]
    s_all = state_scr[j]                                     # (W, N)
    if save_states:
        st_ref[0, 0] = s_all
    e_last = [jnp.exp(v) for v in blk.cs_last]
    y_off = _mm_nt(cmat, _bf(s_all))                         # (Q, W): C Sᵀ
    for t in range(tiles):
        dt_e, dec, dl = blk.decays(t)
        xf = x_ref[0, :, _lanes(t)].astype(jnp.float32) * dt_e   # X = x dt
        xb = _bf(xf)
        cols = [blk.col(t * blk.per_tile + i) for i in range(blk.per_tile)]
        for ti in range(blk.q // TILE):
            y = dec[_rows(ti)] * y_off[_rows(ti), _lanes(t)]
            for i in range(blk.per_tile):
                k = t * blk.per_tile + i
                acc = None
                for tj in range(ti + 1):
                    m = _bf(cb_scr[_rows(ti), _rows(tj)] * blk.ltile(cols[i], k, ti, tj))
                    prod = _mm(m, xb[_rows(tj)])             # (TILE, 128)
                    acc = prod if acc is None else acc + prod
                y = y + blk.own(acc, i)
            y_ref[0, _rows(ti), _lanes(t)] = y
        upd = _mm_tn(_bf(xf * dl), bmat)                     # (128, N)
        for i in range(blk.per_tile):
            k = t * blk.per_tile + i
            r = slice(t * LANES + i * p, t * LANES + (i + 1) * p)
            state_scr[j, r, :] = e_last[k] * s_all[r] + upd[i * p:(i + 1) * p]


def _layout(x, dt, bm, chunk, n_groups):
    bsz, l, h = dt.shape
    p = x.shape[-1] // h
    n = bm.shape[-1] // n_groups
    tiles = _tiles(h, p, n_groups)
    w = tiles * LANES
    heads = w // p
    nblk = h // heads
    bpg = (h // n_groups) // heads
    return bsz, l, h, p, n, w, heads, nblk, l // chunk, bpg


def _dt_rows(dt, heads):
    """(B, L, H) -> (B, H // heads, heads, L): each block's dt as lane rows."""
    bsz, l, h = dt.shape
    return jnp.swapaxes(dt, 1, 2).reshape(bsz, h // heads, heads, l)


def _from_rows(r):
    bsz, nb, heads, l = r.shape
    return jnp.swapaxes(r.reshape(bsz, nb * heads, l), 1, 2)


def _in_specs(chunk, w, heads, n, bpg):
    return [
        pl.BlockSpec((1, heads, 1), lambda b, c, j: (j, 0, 0)),
        pl.BlockSpec((1, chunk, w), lambda b, c, j: (b, c, j)),
        pl.BlockSpec((1, 1, heads, chunk), lambda b, c, j: (b, j, 0, c)),
        pl.BlockSpec((1, chunk, n), lambda b, c, j: (b, c, j // bpg)),
        pl.BlockSpec((1, chunk, n), lambda b, c, j: (b, c, j // bpg)),
    ]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("chunk", "n_groups", "save_states", "interpret"))
def ssd_chunk_scan_fwd(x, dt, a, bm, cm, *, chunk: int, n_groups: int,
                       save_states: bool = True, interpret: bool = True):
    """Forward kernel: y (B, L, H*P) f32 and, with ``save_states``, each
    chunk's incoming state (B, C, H*P, N) f32."""
    bsz, l, h, p, n, w, heads, nblk, nc, bpg = _layout(x, dt, bm, chunk, n_groups)
    out_shape = [jax.ShapeDtypeStruct((bsz, l, h * p), jnp.float32)]
    out_specs = [pl.BlockSpec((1, chunk, w), lambda b, c, j: (b, c, j))]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, h * p, n), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, w, n), lambda b, c, j: (b, c, j, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, tiles=w // LANES,
                          blocks_per_group=bpg, save_states=save_states),
        name="ssd_chunk_scan",
        grid=(bsz, nc, nblk),
        in_specs=_in_specs(chunk, w, heads, n, bpg),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((nblk, w, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((heads, chunk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(a.reshape(nblk, heads, 1), x, _dt_rows(dt, heads), bm, cm)
    return tuple(out) if save_states else out[0]


# -- backward ----------------------------------------------------------------

def _bwd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, st_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                dstate_scr, cb_scr, dg_scr, db_scr, dc_scr, cs_scr, *, p, tiles,
                blocks_per_group):
    ci, j = pl.program_id(1), pl.program_id(2)
    q = x_ref.shape[1]

    @pl.when(ci == 0)
    def _():
        dstate_scr[j] = jnp.zeros(dstate_scr.shape[1:], jnp.float32)

    @pl.when(j % blocks_per_group == 0)
    def _():
        cb_scr[...] = _masked_cb(c_ref[0], b_ref[0])
        dg_scr[...] = jnp.zeros(dg_scr.shape, jnp.float32)
        db_scr[...] = jnp.zeros(db_scr.shape, jnp.float32)
        dc_scr[...] = jnp.zeros(dc_scr.shape, jnp.float32)

    cmat, bmat = c_ref[0], b_ref[0]
    a_blk = a_ref[0]                                         # (heads, 1)
    blk = _Block(dt_ref[0, 0], a_blk, p, cs_scr)
    heads, n_rows = a_blk.shape[0], q // TILE
    s_all, dsn_all = st_ref[0, 0], dstate_scr[j]             # (W, N) each
    dsnb = _bf(dsn_all)
    e_last = [jnp.exp(v) for v in blk.cs_last]
    y_off = _mm_nt(cmat, _bf(s_all))                         # (Q, W): C Sᵀ
    b_dsn = _mm_nt(bmat, dsnb)                               # (Q, W): B dSnᵀ
    # d cs per head as a (1, Q) row: Σ_j dLs[i, j] - Σ_i dLs[i, j] from the
    # intra-chunk scores, plus the off-diagonal output's and the state's
    # decays.  Row sums are taken as column sums of a transpose, together
    # with the per-lane terms of the same head.
    lane_q = _iota((1, q), 1)
    dcs_rows, xdot_rows = [None] * heads, [None] * heads
    for t in range(tiles):
        dt_e, dec, dl = blk.decays(t)
        xraw = x_ref[0, :, _lanes(t)].astype(jnp.float32)
        xf = xraw * dt_e                                     # X = x dt
        xb = _bf(xf)
        dy = dy_ref[0, :, _lanes(t)]
        ddy = dec * dy
        dc_scr[...] += _mm(_bf(ddy), _bf(s_all[_lanes(t)]))  # y_off -> C
        db_scr[...] += _mm(_bf(xf * dl), dsnb[_lanes(t)])    # state -> B
        dsp = _mm_tn(_bf(ddy), cmat)                         # y_off -> S
        b_dsn_t = b_dsn[:, _lanes(t)]
        st_dl = xf * b_dsn_t * dl            # d cs through exp(cs[-1] - cs), per lane
        lane_terms = dy * y_off[:, _lanes(t)] * dec - st_dl  # and through exp(cs)
        dx = [(dl * b_dsn_t)[_rows(i)] for i in range(n_rows)]
        for i in range(blk.per_tile):
            k = t * blk.per_tile + i
            dyk = [_bf(blk.own(dy[_rows(r)], i)) for r in range(n_rows)]
            col = blk.col(k)
            rows, cols = [None] * n_rows, [None] * n_rows
            for ti, tj in blk.pairs():
                cbt = cb_scr[_rows(ti), _rows(tj)]
                lt = blk.ltile(col, k, ti, tj)
                dx[tj] = dx[tj] + _mm_tn(_bf(cbt * lt), dyk[ti])   # Mᵀ dY
                dml = _mm_nt(dyk[ti], xb[_rows(tj)]) * lt
                dg_scr[_rows(ti), _rows(tj)] += dml
                dls = dml * cbt
                cs = jnp.sum(dls, axis=0, keepdims=True)
                rows[ti] = dls if rows[ti] is None else rows[ti] + dls
                cols[tj] = cs if cols[tj] is None else cols[tj] + cs
            v = jnp.concatenate(rows, axis=0) + blk.own(lane_terms, i)  # (Q, 128)
            r = slice(i * p, (i + 1) * p)                    # head k's rows of tile t
            hr = slice(t * LANES + i * p, t * LANES + (i + 1) * p)
            de = jnp.sum(jnp.sum(dsn_all[hr] * s_all[hr], axis=1, keepdims=True),
                         axis=0, keepdims=True)
            dcs_last = (jnp.sum(jnp.sum(blk.own(st_dl, i), axis=1, keepdims=True),
                                axis=0, keepdims=True) + de * e_last[k])
            dcs_rows[k] = (jnp.sum(v.T, axis=0, keepdims=True)
                           - jnp.concatenate(cols, axis=1)
                           + jnp.where(lane_q == q - 1, dcs_last, 0.0))
            dstate_scr[j, hr, :] = e_last[k] * dsn_all[hr] + dsp[r]
        dx = jnp.concatenate(dx, axis=0)                     # (Q, 128)
        xd_t = (dx * xraw).T                                 # (128, Q)
        for i in range(blk.per_tile):
            xdot_rows[t * blk.per_tile + i] = jnp.sum(xd_t[i * p:(i + 1) * p], axis=0,
                                                      keepdims=True)
        dx_ref[0, :, _lanes(t)] = _bf(dx * dt_e)

    sub = _iota((heads, q), 0)
    dcs = jnp.zeros((heads, q), jnp.float32)
    xdot = jnp.zeros((heads, q), jnp.float32)
    for k in range(heads):
        dcs = jnp.where(sub == k, dcs_rows[k], dcs)
        xdot = jnp.where(sub == k, xdot_rows[k], xdot)
    da = _scan_lanes(dcs, reverse=True)                      # d (A dt)
    da_ref[0, 0] = da
    ddt_ref[0, 0] = xdot + da * a_blk

    @pl.when(j % blocks_per_group == blocks_per_group - 1)
    def _():
        dg = dg_scr[...]        # the L tiles read 1 above the diagonal
        dgb = _bf(jnp.where(_iota(dg.shape, 0) >= _iota(dg.shape, 1), dg, 0.0))
        dc_ref[0] = _bf(dc_scr[...] + _mm(dgb, bmat))
        db_ref[0] = _bf(db_scr[...] + _mm_tn(dgb, cmat))


@functools.partial(jax.jit, static_argnames=("chunk", "n_groups", "interpret"))
def ssd_chunk_scan_bwd(x, dt, a, bm, cm, states, dy, *, chunk: int,
                       n_groups: int, interpret: bool = True):
    """Backward kernel: (dx, d dt, dA, dB, dC) for the cotangent ``dy`` of
    :func:`ssd_chunk_scan_fwd`'s y, from its inputs and saved states."""
    bsz, l, h, p, n, w, heads, nblk, nc, bpg = _layout(x, dt, bm, chunk, n_groups)

    def rev(spec):      # the same block, chunks visited last to first
        return pl.BlockSpec(spec.block_shape, lambda b, c, j, f=spec.index_map: f(b, nc - 1 - c, j))

    rows = jax.ShapeDtypeStruct((bsz, nblk, heads, l), jnp.float32)
    in_specs = _in_specs(chunk, w, heads, n, bpg) + [
        pl.BlockSpec((1, 1, w, n), lambda b, c, j: (b, c, j, 0)),
        pl.BlockSpec((1, chunk, w), lambda b, c, j: (b, c, j)),
    ]
    out_specs = [
        pl.BlockSpec((1, chunk, w), lambda b, c, j: (b, c, j)),
        pl.BlockSpec((1, 1, heads, chunk), lambda b, c, j: (b, j, 0, c)),
        pl.BlockSpec((1, 1, heads, chunk), lambda b, c, j: (b, j, 0, c)),
        pl.BlockSpec((1, chunk, n), lambda b, c, j: (b, c, j // bpg)),
        pl.BlockSpec((1, chunk, n), lambda b, c, j: (b, c, j // bpg)),
    ]
    dx, ddt, da, dbm, dcm = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, tiles=w // LANES, blocks_per_group=bpg),
        name="ssd_chunk_scan_bwd",
        grid=(bsz, nc, nblk),
        in_specs=[in_specs[0]] + [rev(s) for s in in_specs[1:]],
        out_specs=[rev(s) for s in out_specs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), rows, rows,
                   jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype)],
        scratch_shapes=[pltpu.VMEM((nblk, w, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, n), jnp.float32),
                        pltpu.VMEM((chunk, n), jnp.float32),
                        pltpu.VMEM((heads, chunk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(a.reshape(nblk, heads, 1), x, _dt_rows(dt, heads), bm, cm, states, dy)
    da_lh = _from_rows(da)
    return dx, _from_rows(ddt), jnp.sum(da_lh * dt, axis=(0, 1)), dbm, dcm


# -- differentiable op -------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def ssd_chunk_scan(x, dt, a, bm, cm, chunk: int, n_groups: int, interpret: bool):
    """y = the chunked SSD scan of (x, dt, A, B, C); see the module docstring."""
    return ssd_chunk_scan_fwd(x, dt, a, bm, cm, chunk=chunk, n_groups=n_groups,
                              save_states=False, interpret=interpret)


def _vjp_fwd(x, dt, a, bm, cm, chunk, n_groups, interpret):
    y, states = ssd_chunk_scan_fwd(x, dt, a, bm, cm, chunk=chunk,
                                   n_groups=n_groups, interpret=interpret)
    return y, (x, dt, a, bm, cm, states)


def _vjp_bwd(chunk, n_groups, interpret, res, dy):
    x, dt, a, bm, cm, states = res
    return ssd_chunk_scan_bwd(x, dt, a, bm, cm, states, dy, chunk=chunk,
                              n_groups=n_groups, interpret=interpret)


ssd_chunk_scan.defvjp(_vjp_fwd, _vjp_bwd)
