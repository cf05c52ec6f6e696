"""Cooperative column-sharded partial LU of big fronts over a mesh axis.

The tree-top problem: the highest etree levels hold one-to-three huge
separator fronts, and a front-per-device partition (ops/batched) leaves
every other chip idle while one factors the root — an Amdahl cap the
reference avoids by distributing each supernode's panels 2D
block-cyclically over the whole process grid (SRC/superlu_defs.h:357-382
block-to-process map; panel broadcasts in SRC/pdgstrf.c:1108).

This is the TPU-native analog for those groups: every device assembles
the SAME front (replicated — vals and the gathered update slab are
already device-local), then a right-looking blocked LU runs with

  * the narrow (mb × pb) panel factorization replicated on all devices
    (O(mb·wb·pb) redundant work — the scalar critical path is latency-,
    not FLOP-bound, so replication beats a broadcast round-trip), and
  * the O(wb·mb²) trailing GEMM sharded by CONTIGUOUS COLUMN SLICES:
    device d owns global columns [d·cb, (d+1)·cb) and updates only its
    slice each panel step.

Communication per front: one (mb, pb) psum per panel step (collecting
the next panel's columns from their owner) plus one final all_gather
of the disjoint trailing column slices to recombine the Schur
complement — ~mb² words over ICI, the same order as a single front
broadcast, versus the reference's per-panel broadcasts.  The
recombination broadcast is the price of the replicated-parent design;
it was measured at ~64% of step traffic at 16 devices, which is why
this scheme is now the LEGACY path (SLU_COOP_SHARDED=0): the sharded
coop chain (ops/coop_sharded.py, DESIGN.md §5) keeps Schur slices
device-local and is the production default.

The result F is bitwise identical on every device, so the caller's
panel extraction, inverse preparation and slab writes run unchanged
(ops/batched._factor_group_impl); only the tiny-pivot counters must be
taken from one device (they are replicated too).

Scopes: the panel psums are `slu.coop.psum`, the recombination
all_gather `slu.coop.gather` (one vocabulary with ops/coop_sharded.py,
whose chain has psums only).  `_panel_eliminate_planes` is the panel
chain in pair arithmetic (complex on real/imaginary planes,
ops/pair_lu), which the sharded chain's pair port shares; this legacy
scheme itself has no pair port.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .batched import psum_exact as _psum
from .dense_lu import _newton_tri_inverse, _tiny_replace, _DIAG_UNROLL
from .pair_lu import (_map, _sub, _tiny_replace_planes, _where, pdiv,
                      pmul)


def _pick_pb(wb: int, pb_max: int = 64) -> int:
    """Largest divisor of wb that is ≤ pb_max (wb buckets live on the
    {2^k, 1.5·2^k} grid so a power-of-two divisor always exists)."""
    if wb <= pb_max:
        return wb
    for d in range(pb_max, 0, -1):
        if wb % d == 0:
            return d
    return 1


def _panel_eliminate(P, k0, thresh, *, pb: int, mb: int):
    """Rank-1 elimination of the pb panel columns of P (mb, pb) whose
    pivot rows sit at the traced global offset k0 (pivot of local
    column t is global row k0 + t).  Rows above k0 (finished U) are
    untouched.  Same masked formulation as dense_lu._rank1_step, with
    the chain chunk-unrolled inside a fori_loop."""
    dtype = P.dtype
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, pb), 1)

    def step(t, carry):
        P, tiny, nzero = carry
        g = k0 + t
        is_t = cols == t
        ck = jnp.sum(jnp.where(is_t, P, 0), axis=1, keepdims=True)
        piv = jnp.sum(jnp.where(rows == g, ck, 0))
        piv, was_tiny, was_zero = _tiny_replace(piv, thresh, dtype)
        below = rows > g
        scaled = jnp.where(below, ck / piv, ck)
        newcol = jnp.where(rows == g, piv, scaled)
        P = jnp.where(is_t, newcol, P)
        rk = jnp.sum(jnp.where(rows == g, P, 0), axis=0,
                     keepdims=True)
        P = P - jnp.where(below, scaled, 0) * jnp.where(cols > t, rk, 0)
        return P, tiny + was_tiny, nzero + was_zero

    cu = max(1, min(_DIAG_UNROLL, pb))
    while pb % cu:
        cu -= 1

    def chunk(c, carry):
        for i in range(cu):
            carry = step(c * cu + i, carry)
        return carry

    zero = jnp.zeros((), jnp.int32)
    return jax.lax.fori_loop(0, pb // cu, chunk, (P, zero, zero))


def _panel_eliminate_planes(P, k0, thresh, *, pb: int, mb: int):
    """`_panel_eliminate` on planes P = (Pr, Pi), each (mb, pb): the
    same masked rank-1 chain with the pivot's division and the outer
    product in pair arithmetic (ops/pair_lu; elementwise, exact like
    the real chain's broadcast multiply)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, pb), 1)

    def step(t, carry):
        P, tiny, nzero = carry
        g = k0 + t
        is_t = cols == t
        at_g = rows == g
        ck = _map(lambda p: jnp.sum(jnp.where(is_t, p, 0), axis=1,
                                    keepdims=True), P)
        piv = _map(lambda c: jnp.sum(jnp.where(at_g, c, 0)), ck)
        piv, was_tiny, was_zero = _tiny_replace_planes(piv, thresh)
        below = rows > g
        scaled = _where(below, pdiv(ck, piv), ck)
        P = _where(is_t, _where(at_g, piv, scaled), P)
        rk = _map(lambda p: jnp.sum(jnp.where(at_g, p, 0), axis=0,
                                    keepdims=True), P)
        upd = pmul(_map(lambda s: jnp.where(below, s, 0), scaled),
                   _map(lambda r: jnp.where(cols > t, r, 0), rk))
        return _sub(P, upd), tiny + was_tiny, nzero + was_zero

    cu = max(1, min(_DIAG_UNROLL, pb))
    while pb % cu:
        cu -= 1

    def chunk(c, carry):
        for i in range(cu):
            carry = step(c * cu + i, carry)
        return carry

    zero = jnp.zeros((), jnp.int32)
    return jax.lax.fori_loop(0, pb // cu, chunk, (P, zero, zero))


def _coop_lu_one(F, thresh, *, wb: int, mb: int, mbp: int, cb: int,
                 pb: int, axis):
    """Cooperative partial LU of ONE front.  F (mb, mbp) is the
    column-padded front, replicated across `axis` on entry; on exit it
    is the factored front, again replicated (bitwise identical on all
    devices).  Only this device's column slice [dev·cb, dev·cb+cb) is
    kept current through the trailing updates; panel columns are
    recombined by psum as they are reached."""
    dev = jax.lax.axis_index(axis)
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    cols_pb = jax.lax.broadcasted_iota(jnp.int32, (1, pb), 1)
    cols_cb = jax.lax.broadcasted_iota(jnp.int32, (1, cb), 1)
    my0 = (dev * cb).astype(jnp.int32)
    zero_i = jnp.zeros((), jnp.int32)

    def panel_step(p, carry):
        F, tiny, nzero = carry
        k0 = p * pb
        # collect the current panel from its column owners (columns of
        # one panel may straddle an ownership boundary)
        panel = jax.lax.dynamic_slice(F, (0, k0), (mb, pb))
        own = (k0 + cols_pb) // cb == dev
        with jax.named_scope("slu.coop.psum"):
            panel = _psum(jnp.where(own, panel, 0), axis)
        panel, t_g, z_g = _panel_eliminate(panel, k0, thresh,
                                           pb=pb, mb=mb)
        tiny, nzero = tiny + t_g, nzero + z_g
        # finalized panel columns are written back on every device
        F = jax.lax.dynamic_update_slice(F, panel, (0, k0))
        # unit-lower diagonal block inverse (replicated, tiny)
        D = jax.lax.dynamic_slice(panel, (k0, 0), (pb, pb))
        eyep = jnp.eye(pb, dtype=F.dtype)
        rp = jax.lax.broadcasted_iota(jnp.int32, (pb, pb), 0)
        cp = jax.lax.broadcasted_iota(jnp.int32, (pb, pb), 1)
        L11 = jnp.where(rp > cp, D, 0) + eyep
        L11i = _newton_tri_inverse(L11, lower=True, unit=True)
        # my column slice: U12 row block + trailing GEMM, only here
        mysl = jax.lax.dynamic_slice(F, (zero_i, my0), (mb, cb))
        rowp = jax.lax.dynamic_slice(
            mysl, (jnp.asarray(k0, jnp.int32), zero_i), (pb, cb))
        ahead = my0 + cols_cb >= k0 + pb       # strictly after panel
        U12 = jnp.where(ahead, L11i @ rowp, rowp)
        mysl = jax.lax.dynamic_update_slice(
            mysl, U12, (jnp.asarray(k0, jnp.int32), zero_i))
        Lcol = jnp.where(rows > k0 + pb - 1, panel, 0)
        mysl = mysl - Lcol @ jnp.where(ahead, U12, 0)
        F = jax.lax.dynamic_update_slice(F, mysl, (zero_i, my0))
        return F, tiny, nzero

    zero = jnp.zeros((), jnp.int32)
    F, tiny, nzero = jax.lax.fori_loop(0, wb // pb, panel_step,
                                       (F, zero, zero))
    # Recombine: panel columns (< wb) are final everywhere; trailing
    # columns are current on their owner only.  The owners' slices are
    # DISJOINT, so this is an all_gather of contiguous (mb, cb) column
    # slices, not a reduction — half the wire cost of the earlier
    # zero-masked psum (all-reduce moves every byte twice) and no
    # floating-point adds at all.  Values are bitwise identical.
    if wb < mbp:
        mysl = jax.lax.dynamic_slice(F, (zero_i, my0), (mb, cb))
        with jax.named_scope("slu.coop.gather"):
            allsl = jax.lax.all_gather(mysl, axis)    # (ndev, mb, cb)
        full = jnp.moveaxis(allsl, 0, 1).reshape(mb, mbp)
        F = jnp.concatenate([F[:, :wb], full[:, wb:]], axis=1)
    return F, tiny, nzero


def coop_partial_lu_batch(F, thresh, *, wb: int, ndev: int, axis):
    """Drop-in for dense_lu.partial_lu_batch for replicated coop
    groups: F (N, mb, mb) identical across `axis`; returns the
    factored batch (again identical on every device) plus the
    replicated tiny/zero-pivot counts (callers must count them on ONE
    device).  `ndev` is the static mesh-axis size."""
    N, mb, _ = F.shape
    cb = -(-mb // ndev)
    mbp = cb * ndev
    pb = _pick_pb(wb)
    if mbp > mb:
        F = jnp.pad(F, ((0, 0), (0, 0), (0, mbp - mb)))
    fn = functools.partial(_coop_lu_one, wb=wb, mb=mb, mbp=mbp,
                           cb=cb, pb=pb, axis=axis)
    thresh = jnp.asarray(thresh, dtype=jnp.asarray(F).real.dtype)
    Fs, tinys, nzeros = jax.vmap(lambda x: fn(x, thresh))(F)
    return Fs[:, :, :mb], jnp.sum(tinys), jnp.sum(nzeros)
