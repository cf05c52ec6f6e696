"""Device sparse matrix–vector/matrix products (pdgsmv analog).

The reference builds a halo-exchange communication schedule for
y = A·x on the distributed CSR (pdgsmv_init/pdgsmv, SRC/pdgsmv.c,
pdgsmv_comm_t SRC/superlu_ddefs.h:275-293).  On a TPU mesh the x
vector lives replicated (or sharded with an all_gather) in HBM, so the
"communication schedule" collapses into a device product.  Two
layouts serve it:

  * COO gather → multiply → segment-scatter-add (the original
    formulation).  XLA lowers the row scatter-add as a serialized
    kCustom fusion: measured 600 MB/s on v5e for the n=27k bench
    residual (pre-round chip record, not re-measured) — ~0.1% of
    HBM bandwidth.
  * padded ELL (default): each row stores a fixed-width band of
    column indices/values; y = rowsum(vals · x[cols]) is a pure
    gather + reduction, NO scatter at all.  The pad slots carry
    column-index n (the shared drop sentinel; gathers clamp, the
    zero pad value kills the lane) so empty rows and ragged tails
    cost nothing but the pad fraction of bandwidth.

`SLU_SPMV_LAYOUT` selects: `ell` forces, `coo` restores the old
formulation, `auto` (default) picks ELL unless the max-row-degree
padding would exceed `SLU_SPMV_ELL_WASTE`× the true nnz (a single
dense-ish row would otherwise square the traffic).

The same routines serve the iterative-refinement residual (pdgsrfs)
and the |A|·|x| backward-error denominator.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags

from ..sparse import CSRMatrix


@jax.named_scope("slu.resid")
def coo_spmv(rows, cols, vals, x, n: int):
    """y = A·x with A given as COO arrays; x is (n,) or (n, nrhs).
    All jittable; rows/cols may be padded with index n (dropped)."""
    gathered = vals[:, None] * x[cols] if x.ndim == 2 else vals * x[cols]
    shape = (n + 1,) + x.shape[1:]
    y = jnp.zeros(shape, gathered.dtype).at[rows].add(
        gathered, mode="drop")
    return y[:n]


def ell_from_csr(indptr, indices, nnz: int | None = None):
    """Host-side padded-ELL index build from CSR structure (the
    pdgsmv_init analog for the scatter-free layout).

    Returns (src, cols): both (n_rows, w) with w = max row degree.
    `src[i, k]` indexes the k-th stored entry of row i in the CSR
    value array — pad slots point at `nnz` (callers gather from a
    value array extended with one zero, so pads contribute exactly
    0).  `cols` carries the matching column indices, pad slots at
    n_cols-sentinel supplied by the caller via `fill_col`."""
    indptr = np.asarray(indptr, dtype=np.int64)
    if nnz is None:
        nnz = int(indptr[-1])
    counts = np.diff(indptr)
    n_rows = len(counts)
    w = int(counts.max(initial=0))
    w = max(w, 1)                      # keep a well-formed (n, 1) pad
    src = np.full((n_rows, w), nnz, dtype=np.int64)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), counts)
    slot = np.arange(len(indices), dtype=np.int64) \
        - np.repeat(indptr[:-1], counts)
    src[rows, slot] = np.arange(len(indices), dtype=np.int64)
    return src, w


def ell_cols_from_src(src, indices, n_cols: int):
    """Column-index plane of the ELL build: pad slots carry the
    drop sentinel `n_cols` (matching coo_spmv's pad convention)."""
    idx = np.concatenate([np.asarray(indices, dtype=np.int64),
                          np.asarray([n_cols], dtype=np.int64)])
    return idx[np.minimum(src, len(idx) - 1)]


@jax.named_scope("slu.resid")
def ell_spmv(ell_cols, ell_vals, x):
    """y = A·x with A in padded-ELL form: per-row gather of the fixed
    band + row-sum reduction — zero scatter ops in the lowered HLO.

    `ell_cols` (n, w) column indices (pad → n: the gather clamps to
    row n-1 and the zero pad value in `ell_vals` kills the lane,
    exactly coo_spmv's drop arithmetic); `ell_vals` (n, w) matching
    values with 0 at pads; x (n,) or (n, nrhs)."""
    xg = x[ell_cols]                       # (n, w[, nrhs]) pure gather
    if x.ndim == 2:
        return jnp.einsum("nw,nwr->nr", ell_vals, xg)
    return jnp.sum(ell_vals * xg, axis=1)


@jax.named_scope("slu.resid")
def ell_spmv_df64(ell_cols, vals_hi, vals_lo, x_hi, x_lo):
    """Double-word accumulation lane of the ELL product: A and x as
    exact (hi, lo) fp32 pairs, the band reduction compensated — the
    residual r = b − A·x of mixed-precision refinement carries ~2×
    fp32 precision with zero f64 ops and zero scatters (kernels in
    precision/doubleword.py; this is the lane
    ops/batched.make_fused_solver rides under
    residual_mode="doubleword")."""
    from ..precision.doubleword import df64_ell_spmv
    return df64_ell_spmv(ell_cols, vals_hi, vals_lo, x_hi, x_lo)


@jax.named_scope("slu.resid")
def coo_spmv_df64(rows, cols, vals_hi, vals_lo, x_hi, x_lo, n: int):
    """Double-word COO lane: per-term products are exact df64, but the
    row scatter-add cannot carry a compensated sum, so accumulation
    stays fp32-class — strictly better than plain fp32, strictly
    worse than the ELL lane (see precision/doubleword.df64_coo_spmv).
    Exists so SLU_SPMV_LAYOUT=coo keeps working under a doubleword
    policy; auto forces ELL there."""
    from ..precision.doubleword import df64_coo_spmv
    return df64_coo_spmv(rows, cols, vals_hi, vals_lo, x_hi, x_lo, n)


def _ell_waste_limit() -> float:
    try:
        return flags.env_float("SLU_SPMV_ELL_WASTE", 4.0)
    except ValueError:
        return 4.0


def spmv_layout(nnz: int, n_rows: int, w: int) -> str:
    """Resolve the residual-SpMV layout: SLU_SPMV_LAYOUT = ell | coo |
    auto (default).  Auto takes ELL unless the fixed-band padding
    exceeds the waste limit — a near-dense row would turn the O(nnz)
    product into O(n·w)."""
    mode = flags.env_str("SLU_SPMV_LAYOUT", "auto").strip().lower()
    if mode in ("ell", "coo"):
        return mode
    return ("ell" if w * n_rows <= _ell_waste_limit() * max(nnz, 1)
            else "coo")


@dataclasses.dataclass
class DeviceSpMV:
    """Cached device SpMV operands (the pdgsmv_init product): COO
    arrays always, plus the padded-ELL planes when the layout
    resolves to ELL (spmv_layout)."""
    n: int
    rows: jnp.ndarray
    cols: jnp.ndarray
    vals: jnp.ndarray
    abs_vals: jnp.ndarray
    layout: str = "coo"
    ell_cols: jnp.ndarray | None = None
    ell_vals: jnp.ndarray | None = None
    ell_abs: jnp.ndarray | None = None
    # doubleword planes (build(..., doubleword=True)): the exact fp32
    # (hi, lo) split of the ORIGINAL f64 values, expanded to the
    # layout's value planes — matvec_df64's operands
    vals_lo: jnp.ndarray | None = None
    ell_vals_lo: jnp.ndarray | None = None

    @classmethod
    def build(cls, a: CSRMatrix, dtype=None,
              doubleword: bool = False) -> "DeviceSpMV":
        rows, cols, vals = a.to_coo()
        vals64 = np.asarray(vals)
        if dtype is not None:
            vals = vals.astype(dtype)
        if doubleword:
            from ..precision.doubleword import split_f64
            v_hi, v_lo = split_f64(vals64)
            vals = v_hi          # the hi plane IS the fp32 value set
        idt = jnp.int32 if a.n < 2**31 - 1 else jnp.int64
        src, w = ell_from_csr(a.indptr, a.indices)
        layout = spmv_layout(len(vals), a.m, w)
        if doubleword and layout != "ell" \
                and flags.env_str("SLU_SPMV_LAYOUT",
                                  "auto").strip().lower() != "coo":
            # precision outranks the pad-waste heuristic for df64
            # residuals (the COO lane's scatter sum stays fp32-class)
            layout = "ell"
        ell_c = ell_v = ell_a = ell_l = low = None
        if doubleword:
            low = jnp.asarray(v_lo)
        if layout == "ell":
            # host-side one-time expansion (vals are static here, so
            # the per-call gather the fused solver needs is skipped)
            ve = np.concatenate([vals, np.zeros(1, vals.dtype)])
            ell_c = jnp.asarray(ell_cols_from_src(src, cols, a.n),
                                dtype=idt)
            ell_v = jnp.asarray(ve[src])
            ell_a = jnp.asarray(np.abs(ve)[src])
            if doubleword:
                le = np.concatenate([v_lo, np.zeros(1, v_lo.dtype)])
                ell_l = jnp.asarray(le[src])
        return cls(n=a.n,
                   rows=jnp.asarray(rows, dtype=idt),
                   cols=jnp.asarray(cols, dtype=idt),
                   vals=jnp.asarray(vals),
                   abs_vals=jnp.asarray(np.abs(vals)),
                   layout=layout, ell_cols=ell_c, ell_vals=ell_v,
                   ell_abs=ell_a, vals_lo=low, ell_vals_lo=ell_l)

    def matvec(self, x):
        if self.layout == "ell":
            return ell_spmv(self.ell_cols, self.ell_vals, x)
        return coo_spmv(self.rows, self.cols, self.vals, x, self.n)

    def absmatvec(self, x):
        if self.layout == "ell":
            return ell_spmv(self.ell_cols, self.ell_abs, x)
        return coo_spmv(self.rows, self.cols, self.abs_vals, x, self.n)

    def matvec_df64(self, x_hi, x_lo):
        """y = A·x in double-word precision (build with
        doubleword=True first); returns the (hi, lo) pair."""
        if self.vals_lo is None:
            raise ValueError("DeviceSpMV was not built with "
                             "doubleword=True")
        if self.layout == "ell":
            return ell_spmv_df64(self.ell_cols, self.ell_vals,
                                 self.ell_vals_lo, x_hi, x_lo)
        return coo_spmv_df64(self.rows, self.cols, self.vals,
                             self.vals_lo, x_hi, x_lo, self.n)


# --------------------------------------------------------------------
# HLO contract registry declarations (tools/slulint/contracts.py)
# --------------------------------------------------------------------

def _contract_build_residual_ell():
    import jax
    import jax.numpy as jnp

    from ..options import Options
    from ..ops.batched import make_fused_solver
    from ..plan.plan import plan_factorization
    from ..utils.testmat import laplacian_2d
    a = laplacian_2d(10)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    step = make_fused_solver(plan, dtype="float32")
    fn = jax.jit(step.resid_fn)
    return fn, (jnp.zeros(len(plan.coo_rows)),
                jnp.zeros((a.n, 2)), jnp.zeros((a.n, 2))), {}


HLO_CONTRACTS = (
    {"name": "residual.ell_spmv",
     "env": {"SLU_SPMV_LAYOUT": "ell"},
     "contracts": ("no_scatter", "no_host_callback"),
     "build": _contract_build_residual_ell,
     "note": "the jitted refinement residual is the per-iteration "
             "hot loop; ELL exists to keep it scatter-free (PR 1 — "
             "scatters ran at 50-600 MB/s on TPU)"},
)
