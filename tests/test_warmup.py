"""Parallel staged-compile warmup (utils/warmup.py): the AOT-compiled
signatures must be exactly the ones staged execution dispatches, so a
warmed persistent cache turns the cold sequential compile into cache
hits."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import Options
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.warmup import staged_signatures, warmup_staged


def _testmat(m=28):
    t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


@pytest.mark.parametrize("arm", ["merged", "legacy"])
def test_warmup_compiles_all_signatures(monkeypatch, arm):
    """The factor segments, and what a sweep on a staged handle
    dispatches: the one packed solve program under the merged
    trisolve arm, a program a distinct group signature each way under
    the legacy one."""
    from superlu_dist_tpu.ops import trisolve
    from superlu_dist_tpu.ops.batched import get_schedule
    monkeypatch.setenv("SLU_TRISOLVE", arm)
    a = _testmat()
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    sched = get_schedule(plan, 1)
    fsigs, ssigs = staged_signatures(sched)
    # force: the tiny test schedule is below the staged-auto
    # threshold, and without forcing the gate correctly refuses to
    # compile programs the run would never dispatch
    gate = warmup_staged(plan, dtype="float32", workers=2)
    assert gate.get("staged_inactive") and gate["factor_programs"] == 0
    rep = warmup_staged(plan, dtype="float32", workers=2, force=True)
    assert rep["factor_programs"] == len(fsigs) > 0
    if arm == "legacy":
        assert rep["sweep_programs"] == 2 * len(ssigs) > 0
        assert all(len(k) == 5 for k in ssigs)
        return
    # one key: every group's (Li, L21, Ui, U12) pack shapes in order
    assert rep["sweep_programs"] == len(ssigs) == 1
    (shapes,) = ssigs
    assert len(shapes) == len(sched.groups)
    assert all(len(grp) == 4 for grp in shapes)
    # warmed through the callable the dispatch calls, which the
    # schedule now holds
    assert trisolve._packed_key("float32", False) in sched._trisolve_fns


def test_staged_run_after_warmup_is_correct(monkeypatch):
    """Warmup must not perturb the real staged execution (same jit
    functions, lowered with the same signatures)."""
    monkeypatch.setenv("SLU_STAGED", "1")
    from superlu_dist_tpu import gssvx
    a = _testmat(24)
    rng = np.random.default_rng(0)
    xtrue = rng.standard_normal(a.n)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    rep = warmup_staged(plan, dtype="float32", workers=2)
    assert rep["sweep_programs"] == 1
    x, lu, stats = gssvx(Options(factor_dtype="float32"), a,
                         a.to_scipy() @ xtrue)
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-10
    # the solve dispatched the program warm-up compiled, and no other
    assert stats.dispatch["sweep_segments"] == 1


# The warmup contract is CROSS-PROCESS: warmup in one process writes
# the persistent compilation cache; the staged dispatch in a LATER
# process (prime the cache once, dispatch in every later process that
# is handed the same cache directory) must hit those entries instead of
# the compiler.  Within one process the check below is meaningless by
# design: `.lower().compile()` populates the in-memory pjit executable
# cache, so a same-process dispatch reuses the executables directly
# and never consults the persistent cache at all (verified: 0
# cache_hits events in-process, 38/38 in a fresh process — the round-3
# red test asserted persistent hits in exactly the one scenario where
# JAX legitimately bypasses the persistent cache).

_COMMON = r"""
import json, os
import numpy as np
import scipy.sparse as sp
import jax
jax.config.update("jax_platforms", "cpu")
# the cache is placed from outside (JAX_COMPILATION_CACHE_DIR in the
# child's environment), the way a chip run's is
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
os.environ["SLU_STAGED"] = "1"
from superlu_dist_tpu import Options, gssvx
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.warmup import staged_signatures, warmup_staged
from superlu_dist_tpu.ops.batched import get_schedule
t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(24, 24))
a = csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())
plan = plan_factorization(a, Options(factor_dtype="float32"))
"""

_WARM_SCRIPT = _COMMON + r"""
# workers=2: PARALLEL warmup, restored after the PR-5 de-flake.  The
# intermittent 1-of-38 key mismatch was chased to its root: with
# workers>=2, concurrent .lower() calls raced on jax's global
# inner-jit trace cache, so a raced outer jaxpr embedded
# equal-but-not-identical sub-jaxpr objects and lowered DUPLICATE
# private helper funcs (@_where_N) — same semantics, different
# serialized module bytes, different persistent-cache key than the
# sequential dispatch computes.  utils/warmup.py now serializes the
# trace/lower phase behind _LOWER_LOCK (lowering is GIL-bound; the
# parallel win is XLA compilation, which releases the GIL), making
# warm keys deterministic at any worker count — verified 10/10
# mismatch-free at workers=2 vs ~1/3 flaky before the fix.
rep = warmup_staged(plan, dtype="float32", workers=2)
print("RESULT " + json.dumps(rep))
"""

_DISPATCH_SCRIPT = _COMMON + r"""
fsigs, ssigs = staged_signatures(get_schedule(plan, 1))
hits, misses = [0], [0]
def _listen(event, *a, **k):
    if event == "/jax/compilation_cache/cache_hits":
        hits[0] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        misses[0] += 1
jax.monitoring.register_event_listener(_listen)
rng = np.random.default_rng(0)
xtrue = rng.standard_normal(a.n)
x, lu, stats = gssvx(Options(factor_dtype="float32"), a,
                     a.to_scipy() @ xtrue)
relerr = float(np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue))
print("RESULT " + json.dumps({"hits": hits[0], "misses": misses[0],
      "fsigs": len(fsigs), "ssigs": len(ssigs), "relerr": relerr}))
"""


def _run_sub(script, cache_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [ln for ln in p.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow     # ~57 s: two fresh subprocesses (write + read
def test_staged_dispatch_hits_warmed_cache(tmp_path):   # the cache)
    """A staged dispatch in a FRESH process must land on the programs a
    previous process's warmup_staged wrote to the persistent cache: the
    factor + packed-sweep compiles must all be persistent-cache HITS
    (counted via jax's /jax/compilation_cache/cache_hits monitoring
    event).  Any drift between warmup's hand-mirrored operand
    signatures and the dispatch site turns warmed programs into dead
    compiles and fails this count.
    (The reference's analogous contract is the setup-vs-numeric split,
    superlu_defs.h:577-598 — plan once, warm once, then every
    SamePattern refactorization is dispatch-only.)"""
    cache_dir = str(tmp_path / "warmcache")
    warm = _run_sub(_WARM_SCRIPT, cache_dir)
    assert warm["factor_programs"] > 0
    assert len(os.listdir(cache_dir)) > 0, \
        "warmup wrote nothing to the cache"
    out = _run_sub(_DISPATCH_SCRIPT, cache_dir)
    assert out["relerr"] < 1e-10
    # factor signatures + the one packed sweep program all hit;
    # other programs (the pack, refinement SpMV etc.) are misses and
    # don't count here
    want = out["fsigs"] + out["ssigs"]
    assert out["hits"] >= want, (out, want)
