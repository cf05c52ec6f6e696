"""Sharded cooperative LU: block-cyclic column ownership on GLOBAL
matrix columns — the successor to the replicated coop scheme
(ops/coop_lu.py; design + measured motivation in DESIGN.md §5).

The replicated scheme's limit, measured at 16 devices: the parent
front replicates, so every tree-top Schur complement must reach every
device — an Ω(mb²) all_gather per coop front that carried ~64% of
predicted step traffic on the n=27k bench matrix (tests/test_coop16).

This scheme keys column ownership on the GLOBAL column id,

    owner(g) = (g // B) % ndev        (SLU_COOP_B, default B = 1)

— the reference's 2D block-cyclic column map (SRC/superlu_defs.h:
357-382) re-rendered for the level-batched front world.  Because a
coop child's trailing (Schur) column and the parent column it
extend-adds into are the SAME global column, they share an owner BY
CONSTRUCTION: the whole coop→coop chain assembles device-locally and
the per-front recombination broadcast disappears.  What remains per
front is O(mb·wb): one (mb, pb) psum per panel step (collecting the
next panel's columns from their owners — the analog of the reference's
panel column broadcast, SRC/pdgstrf.c:1108) and one (wb, mb) U-stripe
psum at the end (so the solve's U panels stay replicated, as the
slab layout requires).  Traffic drops ~(mb/wb)× per coop front.

Storage per device: F_d (mb, cp) holding only the owned columns —
slots [0, tp) are owned TRAILING columns (the front's struct set),
slots [tp, cp) owned PANEL columns.  A host-precomputed position
vector pos (cp,) maps slot → padded front position (sentinel ≥ mb for
padding slots); all panel selection/write-back runs as exact 0/1
one-hot matmuls built from `pos` on device, so the kernel contains no
device-varying static shapes (shard_map traces one program).

The factored outputs are (Pacc, Ustripe, slab): the full (mb, wb)
panel columns and (wb, mb) U stripe replicated on every device
(bitwise identical — both come off psums), and the (mb-wb, tp)
device-local Schur column slice that stays distributed for the next
coop group's extend-add.

Complex on a TPU mesh (utils/platform.complex_lowering: "pair") runs
the same chain on real/imaginary planes, `coop_sharded_lu_pair_batch`:
the 0/1 selection matmuls are real and act plane-wise, the panel
chain, the block inverse, the U stripe row and the trailing GEMM are
ops/pair_lu's arithmetic (four real matmuls a product), and each psum
carries both planes — no complex operation in the program.  The psums
are scoped `slu.coop.psum` in both arithmetics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .batched import psum_exact as _psum
from .coop_lu import (_panel_eliminate, _panel_eliminate_planes,
                      _pick_pb)
from .dense_lu import _newton_tri_inverse
from .pair_lu import (_map, _newton_tri_inverse_planes, _sub, _where,
                      pmatmul)


@jax.named_scope("slu.coop.psum")
def _coop_psum(x, axis):
    """The chain's reduction: one array, or both planes of a pair
    stacked into ONE all-reduce.  With two all-reduces a panel step
    (a psum a plane, or a psum of the tuple) the program that holds
    factor and sweeps together (`factor_dist.make_dist_step`) gave
    wrong values on the XLA:CPU mesh, different from call to call;
    with one it is exact and repeatable
    (tests/test_pair_mesh.py::test_the_fused_dist_step_in_pair_storage)."""
    if isinstance(x, tuple):
        both = _psum(jnp.stack(x), axis)
        return both[0], both[1]
    return _psum(x, axis)


def _coop_sharded_one(Fd, pos, thresh, *, wb: int, mb: int, cp: int,
                      tp: int, pb: int, axis):
    """One front: Fd (mb, cp) owned-column slice, pos (cp,) slot →
    padded front position (sentinel ≥ mb).  Returns (Pacc (mb, wb),
    Ustripe (wb, mb), slab (mb-wb, tp), tiny, nzero); Pacc/Ustripe
    replicated across `axis`, slab device-local."""
    dtype = Fd.dtype
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    posr = pos[None, :].astype(jnp.int32)           # (1, cp)
    tsel = jax.lax.broadcasted_iota(jnp.int32, (1, pb), 1)
    zero_i = jnp.zeros((), jnp.int32)

    def panel_step(p, carry):
        Fd, Pacc, tiny, nzero = carry
        k0 = jnp.asarray(p * pb, jnp.int32)   # x64 mode traces p int64
        # collect the panel's pb columns from their owners: exact 0/1
        # one-hot selection matmul + psum over disjoint contributions
        S = (posr.T == k0 + tsel).astype(dtype)     # (cp, pb)
        panel = _coop_psum(Fd @ S, axis)            # (mb, pb)
        panel, t_g, z_g = _panel_eliminate(panel, k0, thresh,
                                           pb=pb, mb=mb)
        tiny, nzero = tiny + t_g, nzero + z_g
        Pacc = jax.lax.dynamic_update_slice(Pacc, panel, (zero_i, k0))
        # write finalized panel columns back into my owned slots
        inpanel = (posr >= k0) & (posr < k0 + pb)
        Fd = jnp.where(inpanel, panel @ S.T, Fd)
        # unit-lower diagonal-block inverse (replicated, tiny)
        D = jax.lax.dynamic_slice(panel, (k0, zero_i), (pb, pb))
        rp = jax.lax.broadcasted_iota(jnp.int32, (pb, pb), 0)
        cpi = jax.lax.broadcasted_iota(jnp.int32, (pb, pb), 1)
        L11 = jnp.where(rp > cpi, D, 0) + jnp.eye(pb, dtype=dtype)
        L11i = _newton_tri_inverse(L11, lower=True, unit=True)
        # U12 row stripe + trailing GEMM on my owned columns only;
        # padding slots (pos sentinel ≥ mb) satisfy `ahead` but their
        # columns are identically zero, so the update is a no-op there
        ahead = posr >= k0 + pb
        rowp = jax.lax.dynamic_slice(Fd, (k0, zero_i), (pb, cp))
        U12 = jnp.where(ahead, L11i @ rowp, rowp)
        Fd = jax.lax.dynamic_update_slice(Fd, U12, (k0, zero_i))
        Lcol = jnp.where(rows > k0 + pb - 1, panel, 0)
        Fd = Fd - Lcol @ jnp.where(ahead, U12, 0)
        return Fd, Pacc, tiny, nzero

    zero = jnp.zeros((), jnp.int32)
    Pacc0 = jnp.zeros((mb, wb), dtype)
    Fd, Pacc, tiny, nzero = jax.lax.fori_loop(
        0, wb // pb, panel_step, (Fd, Pacc0, zero, zero))
    # U stripe: rows [0, wb) of every column, scattered to front
    # positions (each position owned by exactly one device, padding
    # slots drop out of the one-hot) and psum'd to replication —
    # O(wb·mb), the solve-storage price that replaces the old Ω(mb²)
    # trailing recombination gather
    cols_mb = jax.lax.broadcasted_iota(jnp.int32, (1, mb), 1)
    T = (posr.T == cols_mb).astype(dtype)           # (cp, mb)
    Ustripe = _coop_psum(Fd[:wb, :] @ T, axis)      # (wb, mb)
    slab = Fd[wb:, :tp]                             # (mb-wb, tp)
    return Pacc, Ustripe, slab, tiny, nzero


def _coop_sharded_one_planes(Fd, pos, thresh, *, wb: int, mb: int,
                             cp: int, tp: int, pb: int, axis):
    """`_coop_sharded_one` on planes Fd = (Fr, Fi), each (mb, cp):
    returns (Pacc, Ustripe, slab, tiny, nzero) with the first three as
    plane tuples."""
    rdt = Fd[0].dtype
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    posr = pos[None, :].astype(jnp.int32)           # (1, cp)
    tsel = jax.lax.broadcasted_iota(jnp.int32, (1, pb), 1)
    zero_i = jnp.zeros((), jnp.int32)
    rp = jax.lax.broadcasted_iota(jnp.int32, (pb, pb), 0)
    cpi = jax.lax.broadcasted_iota(jnp.int32, (pb, pb), 1)
    eye = jnp.eye(pb, dtype=rdt)

    def panel_step(p, carry):
        Fd, Pacc, tiny, nzero = carry
        k0 = jnp.asarray(p * pb, jnp.int32)
        # the selections are real 0/1 matrices: plane-wise matmuls
        S = (posr.T == k0 + tsel).astype(rdt)       # (cp, pb)
        panel = _coop_psum(_map(lambda f: f @ S, Fd), axis)
        panel, t_g, z_g = _panel_eliminate_planes(panel, k0, thresh,
                                                  pb=pb, mb=mb)
        tiny, nzero = tiny + t_g, nzero + z_g
        Pacc = tuple(jax.lax.dynamic_update_slice(a, q, (zero_i, k0))
                     for a, q in zip(Pacc, panel))
        inpanel = (posr >= k0) & (posr < k0 + pb)
        Fd = _where(inpanel, _map(lambda q: q @ S.T, panel), Fd)
        D = _map(lambda q: jax.lax.dynamic_slice(
            q, (k0, zero_i), (pb, pb)), panel)
        L11 = (jnp.where(rp > cpi, D[0], 0) + eye,
               jnp.where(rp > cpi, D[1], 0))
        L11i = _newton_tri_inverse_planes(L11, lower=True, unit=True)
        ahead = posr >= k0 + pb
        rowp = _map(lambda f: jax.lax.dynamic_slice(
            f, (k0, zero_i), (pb, cp)), Fd)
        U12 = _where(ahead, pmatmul(L11i, rowp), rowp)
        Fd = tuple(jax.lax.dynamic_update_slice(f, u, (k0, zero_i))
                   for f, u in zip(Fd, U12))
        Lcol = _map(lambda q: jnp.where(rows > k0 + pb - 1, q, 0),
                    panel)
        Fd = _sub(Fd, pmatmul(Lcol, _map(
            lambda u: jnp.where(ahead, u, 0), U12)))
        return Fd, Pacc, tiny, nzero

    zero = jnp.zeros((), jnp.int32)
    Pacc0 = (jnp.zeros((mb, wb), rdt), jnp.zeros((mb, wb), rdt))
    Fd, Pacc, tiny, nzero = jax.lax.fori_loop(
        0, wb // pb, panel_step, (Fd, Pacc0, zero, zero))
    cols_mb = jax.lax.broadcasted_iota(jnp.int32, (1, mb), 1)
    T = (posr.T == cols_mb).astype(rdt)             # (cp, mb)
    Ustripe = _coop_psum(_map(lambda f: f[:wb, :] @ T, Fd), axis)
    slab = _map(lambda f: f[wb:, :tp], Fd)
    return Pacc, Ustripe, slab, tiny, nzero


def coop_sharded_lu_pair_batch(F, pos, thresh, *, wb: int, cp: int,
                               tp: int, axis):
    """`coop_sharded_lu_batch` for pair fronts F (2, N, mb, cp):
    returns stacked (Pacc (2, N, mb, wb), Ustripe (2, N, wb, mb),
    slab (2, N, mb-wb, tp), tiny, nzero)."""
    mb = F.shape[2]
    fn = functools.partial(_coop_sharded_one_planes, wb=wb, mb=mb,
                           cp=cp, tp=tp, pb=_pick_pb(wb), axis=axis)
    Pacc, Ustripe, slab, tinys, nzeros = jax.vmap(
        lambda x, p: fn(x, p, thresh))((F[0], F[1]), pos)
    return (jnp.stack(Pacc), jnp.stack(Ustripe), jnp.stack(slab),
            jnp.sum(tinys), jnp.sum(nzeros))


def coop_sharded_lu_batch(F, pos, thresh, *, wb: int, cp: int,
                          tp: int, axis):
    """Batched sharded-coop LU: F (N, mb, cp) owned-column slices,
    pos (N, cp) slot→position maps.  Returns (Pacc (N, mb, wb),
    Ustripe (N, wb, mb), slab (N, mb-wb, tp), tiny, nzero); the
    replicated counters must be taken from ONE device by the caller."""
    N, mb, _ = F.shape
    pb = _pick_pb(wb)
    fn = functools.partial(_coop_sharded_one, wb=wb, mb=mb, cp=cp,
                           tp=tp, pb=pb, axis=axis)
    thresh = jnp.asarray(thresh, dtype=jnp.asarray(F).real.dtype)
    Pacc, Ustripe, slab, tinys, nzeros = jax.vmap(
        lambda x, p: fn(x, p, thresh))(F, pos)
    return Pacc, Ustripe, slab, jnp.sum(tinys), jnp.sum(nzeros)
