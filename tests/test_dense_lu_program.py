"""The shape of the dense front kernel's program (`ops/dense_lu.py`):
panel first, Schur once.

Pinned here, from the jaxpr: the block loop of `partial_lu` carries the
column panel and the row panel only, so no product inside it has an
mb × mb result; the trailing matrix is updated by exactly one product
of contraction `wb`, outside the loop, under `slu.schur`; and the
factor program's group body (`ops/batched._factor_group_impl`) stores
the three pieces it is handed without reassembling an mb × mb front.
A later edit that puts the whole-front pass back fails here, on the
CPU, before any chip time."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu.ops import batched
from superlu_dist_tpu.ops.dense_lu import (partial_lu, partial_lu_batch,
                                           partial_lu_panels)
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.testmat import laplacian_3d

MB, WB = 1024, 256


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            x = getattr(x, "jaxpr", x)        # a ClosedJaxpr's own
            if hasattr(x, "eqns"):
                yield x


def _walk(jaxpr, in_loop=False, scope=""):
    """(equation, inside a loop at any depth, scope path) of every
    equation of a jaxpr and of the jaxprs nested in it.  A nested
    jaxpr's name stacks start anew, so the path is carried down; a
    fori_loop with static bounds is a `scan`, one with traced bounds
    a `while`."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn, in_loop, here
        inner = in_loop or eqn.primitive.name in ("while", "scan")
        for sub in _subjaxprs(eqn):
            yield from _walk(sub, inner, here)


def _dots(jaxpr):
    """(result shape, contraction size, inside a loop, scope path) of
    every dot_general."""
    out = []
    for eqn, in_loop, scope in _walk(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        (lc, _), _ = eqn.params["dimension_numbers"]
        k = int(np.prod([eqn.invars[0].aval.shape[d] for d in lc]))
        out.append((tuple(eqn.outvars[0].aval.shape), k, in_loop, scope))
    return out


def _concat_shapes(jaxpr):
    return [tuple(eqn.outvars[0].aval.shape) for eqn, *_ in _walk(jaxpr)
            if eqn.primitive.name == "concatenate"]


@pytest.mark.parametrize("fn", [partial_lu, partial_lu_panels],
                         ids=["assembled", "panels"])
def test_block_loop_touches_panels_only_and_schur_is_one_product(fn):
    F = jax.ShapeDtypeStruct((MB, MB), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda f: fn(f, jnp.float32(1e-6), wb=WB))(F)
    dots = _dots(jaxpr.jaxpr)
    loop = [d for d in dots if d[2]]
    # the block loop is there, and walks panels: results are at most
    # mb × wb (column panel) or wb × r (row panel), never the front
    assert any(shape == (MB, WB) for shape, *_ in loop)
    assert any(shape == (WB, MB - WB) for shape, *_ in loop)
    for shape, k, _, stack in loop:
        assert shape[-2:] != (MB, MB), (shape, stack)
        assert int(np.prod(shape)) <= MB * WB, (shape, stack)
        assert k <= 32, (shape, k, stack)
    # the trailing update: one product, K = wb, after the loop
    outside = [d for d in dots if not d[2]]
    assert [(s, k) for s, k, _, _ in outside] == [
        ((MB - WB, MB - WB), WB)]
    assert "slu.schur" in outside[0][3]
    assert "slu.partial_lu" in outside[0][3]
    # nothing else inside the loop claims the Schur scope
    assert not any("slu.schur" in stack for *_, stack in loop)


def test_a_root_front_has_no_schur_product():
    F = jax.ShapeDtypeStruct((WB, WB), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda f: partial_lu_panels(f, jnp.float32(1e-6), wb=WB))(F)
    dots = _dots(jaxpr.jaxpr)
    assert dots and all(in_loop for _, _, in_loop, _ in dots)
    assert not any("slu.schur" in stack for *_, stack in dots)


def _group_body_jaxpr(g, sched, nnz, dtype=np.float32):
    """`_factor_group_impl` traced for one group of a real schedule,
    with the arguments `parallel/factor_dist._factor_loop` gives it."""
    a_src, a_dst, one_dst, ea_blocks, pos_idx = g.dev(squeeze=True)[:5]

    def body(vals, upd_buf, L, U, Li, Ui):
        z = jnp.zeros((), jnp.int32)
        return batched._factor_group_impl(
            vals, upd_buf, L, U, Li, Ui, z, z, jnp.asarray(1e-6, dtype),
            a_src, a_dst, one_dst, ea_blocks,
            jnp.int32(g.upd_off_global), jnp.int32(g.L_off),
            jnp.int32(g.U_off), jnp.int32(g.Li_off), jnp.int32(g.Ui_off),
            mb=g.mb, wb=g.wb, n_pad=g.n_loc, ea_meta=g.ea_meta,
            eb_meta=g.eb_meta, pos_idx=pos_idx)

    flat = lambda n: jax.ShapeDtypeStruct((n,), dtype)
    return jax.make_jaxpr(body)(
        flat(nnz + 1), flat(sched.upd_total + sched.upd_pad),
        flat(sched.L_total), flat(sched.U_total), flat(sched.Li_total),
        flat(sched.Ui_total))


def test_factor_group_body_reassembles_no_front():
    a = laplacian_3d(8)
    plan = plan_factorization(a)
    sched = batched.get_schedule(plan, 1)
    # groups with a trailing matrix and children, whose mb no other
    # square of the body (the wb × wb inverses) can be mistaken for
    groups = [g for g in sched.groups if g.mb > g.wb and g.ea_meta]
    assert groups
    for g in groups:
        jaxpr = _group_body_jaxpr(g, sched, a.nnz)
        shapes = _concat_shapes(jaxpr.jaxpr)
        assert not any(s[-2:] == (g.mb, g.mb) for s in shapes), (
            g.mb, g.wb, shapes)
        # and the one K = wb product is there, under its scope
        schur = [d for d in _dots(jaxpr.jaxpr) if "slu.schur" in d[3]]
        assert [(s[-2:], k, in_loop) for s, k, in_loop, _ in schur] == [
            ((g.mb - g.wb, g.mb - g.wb), g.wb, False)]


def test_the_detector_sees_a_reassembled_front():
    """The whole-front contract (`partial_lu_batch`) does concatenate
    an mb × mb front: what the group body must not contain is
    something this file's reader finds."""
    F = jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda f: partial_lu_batch(f, jnp.float32(1e-6), wb=16))(F)
    assert any(s[-2:] == (64, 64) for s in _concat_shapes(jaxpr.jaxpr))
