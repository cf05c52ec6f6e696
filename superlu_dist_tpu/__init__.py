"""superlu_dist_tpu — a TPU-native distributed sparse direct solver.

A brand-new JAX/XLA/Pallas implementation with the capabilities of
SuperLU_DIST (reference: /root/reference, v8.1.1): sparse LU with static
pivoting (GESP), supernodal numeric factorization over a 2D/3D device
mesh, block-sparse triangular solves, iterative refinement, and a
mixed-precision (low-precision factor + f64 residual) mode.

Design (see SURVEY.md §7): static pivoting makes the numeric phase a
fixed DAG of dense block operations with static shapes — exactly what
XLA wants.  The factorization is formulated multifrontally: each
supernode owns a dense frontal matrix, fronts are padded to a small set
of bucket shapes and batched per elimination-tree level, so the hot loop
is pure batched GEMM/TRSM on the MXU.  Distribution is level-synchronous
sharding over a `jax.sharding.Mesh` with ancestor reductions as `psum`
(the TPU-native analog of the reference's 3D communication-avoiding
algorithm, SRC/pdgstrf3d.c).

Double precision is first-class for a linear solver, so importing this
package enables JAX x64 mode.

It also pins the default matmul precision to "highest": on TPU the
default f32 matmul is a single bf16 MXU pass (~3 decimal digits), which
silently degrades the f32 factorization to bf16 class — measured
err~2.3e-3 vs the f64 ground truth on hardware, versus ~1e-7 for true
f32 (pre-round chip record, not re-measured) — and stalls the f64 iterative-refinement
contract for conditioned matrices (cond·ε_factor must stay < 1,
SURVEY.md §2.6).  Solvers sell accuracy classes, not matmul throughput;
override with SLU_MATMUL_PREC=default|high|highest if you know better.
An application that configured jax_default_matmul_precision BEFORE this
import keeps its setting (the pin only fills an unset default; the hot
factor path additionally scopes "float32" locally via _hi_prec, so the
solver's own numerics never depend on the global).  No effect on CPU
(native f32 there)."""

import jax as _jax

_jax.config.update("jax_enable_x64", True)

from . import flags as _flags  # noqa: E402 — the package env gateway

_prec = _flags.env_opt("SLU_MATMUL_PREC")
if _prec is None and _jax.config.jax_default_matmul_precision is None:
    # only pin when neither the embedding application (jax config) nor
    # the operator (SLU_MATMUL_PREC) has chosen a precision — import
    # order must not silently override an explicit app-wide setting
    _jax.config.update("jax_default_matmul_precision", "highest")
elif _prec is not None and _prec != "default":
    _jax.config.update("jax_default_matmul_precision", _prec)

from .options import (  # noqa: E402
    ColPerm,
    Fact,
    IterRefine,
    Options,
    RowPerm,
    Trans,
    YesNo,
)
from .utils.stats import Stats  # noqa: E402
from .sparse import CSRMatrix, csr_from_coo, csr_from_scipy  # noqa: E402
from .plan.plan import FactorPlan, plan_factorization  # noqa: E402
from .models.gssvx import (  # noqa: E402
    LUFactorization,
    factorize,
    get_diag_u,
    gssvx,
    query_space,
    solve,
    warm_solve,
)
from .batch.engine import (  # noqa: E402
    BatchedLU,
    batch_factorize,
    batch_solve,
)
from .parallel.grid import make_solver_mesh  # noqa: E402
from .parallel.multihost import (  # noqa: E402
    csr_from_row_slices,
    plan_factorization_multihost,
)
from .parallel.psymbfact_dist import (  # noqa: E402
    plan_factorization_dist,
    scaled_values_local,
)
from .utils.io import read_matrix  # noqa: E402
from .precision import PrecisionPolicy, ResidualMode  # noqa: E402
from .autodiff import (  # noqa: E402
    GradResult,
    grad_context,
    sparse_solve,
    vjp_solve,
)

__version__ = "0.1.0"

__all__ = [
    "ColPerm",
    "Fact",
    "IterRefine",
    "Options",
    "RowPerm",
    "Trans",
    "YesNo",
    "Stats",
    "CSRMatrix",
    "csr_from_coo",
    "csr_from_scipy",
    "csr_from_row_slices",
    "FactorPlan",
    "plan_factorization",
    "plan_factorization_dist",
    "plan_factorization_multihost",
    "scaled_values_local",
    "LUFactorization",
    "BatchedLU",
    "batch_factorize",
    "batch_solve",
    "PrecisionPolicy",
    "ResidualMode",
    "GradResult",
    "factorize",
    "get_diag_u",
    "grad_context",
    "gssvx",
    "make_solver_mesh",
    "query_space",
    "read_matrix",
    "solve",
    "sparse_solve",
    "vjp_solve",
    "warm_solve",
    "__version__",
]
