"""Matrix generator `lap3d`: the 7-point Laplacian on a k x k x k grid
(n = k**3), as scipy CSR in float64.  A configuration names its
generator in `matrix.generator`; the runner loads
`configs/gen_<name>.py` and calls `generate(**matrix.args)`, so a
later configuration's generator arrives as a new file here."""

import scipy.sparse as sp


def generate(k: int):
    t = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(k, k))
    a = sp.kronsum(sp.kronsum(t, t), t, format="csr").astype("float64")
    a.sort_indices()
    return a
