"""What the three Pallas TPU kernels (pallas_lu, pallas_scatter,
pallas_lsum) share: the Mosaic dtype rule, the VMEM working-set
budget and the interpret-mode default."""

from __future__ import annotations

import jax
import numpy as np

# each kernel keeps its operands plus an output copy VMEM-resident
# (~16 MB/core on v5e); beyond this the XLA path keeps the bucket
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def mosaic_dtype(dtype) -> bool:
    """Real sub-64-bit dtypes only: Mosaic lowers neither complex nor
    64-bit (the kernels trace under `jax.enable_x64(False)` for the
    same reason — weak Python scalars must enter the jaxpr at 32
    bit)."""
    dtype = np.dtype(dtype)
    return dtype.kind != "c" and dtype.itemsize < 8


def interpret_default() -> bool:
    """Interpret mode exists for the CPU test suite, which runs the
    same kernel bodies through the Pallas interpreter.  On a TPU
    backend this is False: a kernel there always goes through
    Mosaic, and a compile failure surfaces as the compiler's error."""
    return jax.default_backend() != "tpu"
