"""slulint (tools/slulint): green on HEAD, red on every seeded
fixture violation, baseline ratchet + --update roundtrip, HLO
contract registry coverage incl. synthetic reintroductions of the
bug classes it exists to catch (scatter in a trisolve-shaped toy jit,
f64 in a df64 build, the PR 5 flusher self-join, a lock-order cycle,
a static_argnames kwarg call, an untyped serve raise)."""

import json
import os
import subprocess
import sys

import pytest

from tools.slulint import Finding, baseline as bl, locks, rules
from tools.slulint import contracts, default_scan_files, rel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(ROOT, "tests", "fixtures", "slulint")


def _cli(*args, timeout=120):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "tools.slulint", *args],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)


def _fixture(name):
    return os.path.join(FIX, name)


# -- the gate on HEAD -------------------------------------------------

def test_cli_fast_gate_green_on_head():
    """`python -m tools.slulint --no-contracts` exits 0 against the
    committed baseline: AST rules, lock auditor, flag audit."""
    p = _cli("--no-contracts")
    assert p.returncode == 0, p.stdout + p.stderr
    assert "0 new finding" in p.stdout


def test_full_gate_green_on_head_in_process():
    """The contracts pass holds on HEAD (in-process — the subprocess
    variant would re-pay jit warmup; tier-1 runs this once)."""
    findings = contracts.check_all(ROOT)
    assert not findings, "\n".join(f.format() for f in findings)


def test_head_scan_has_no_rule_findings():
    """Rule-level pin independent of the CLI: the default scan set
    yields zero AST/lock findings (the committed baseline is EMPTY —
    every pre-existing violation was fixed, none grandfathered)."""
    files = default_scan_files(ROOT)
    pairs = [(p, rel(p, ROOT)) for p in files]
    out = []
    for ap, rp in pairs:
        out.extend(rules.check_file(ap, rp))
    out.extend(locks.check_paths(
        [(a, r) for a, r in pairs if locks.in_audit_scope(r)]))
    assert not out, "\n".join(f.format() for f in out)
    entries = bl.load(os.path.join(ROOT, bl.BASELINE_NAME))
    assert entries == {}, "baseline should be empty on HEAD"


# -- red on every seeded fixture --------------------------------------

@pytest.mark.parametrize("fixture,rule", [
    ("bad_env.py", "env-read"),
    ("bad_purity.py", "host-call-in-jit"),
    ("bad_dispatch.py", "static-kwarg"),
    ("serve/bad_raise.py", "untyped-raise"),
    ("serve/bad_raise.py", "bare-except"),
    ("bad_locks_cycle.py", "lock-cycle"),
    ("bad_self_join.py", "self-join"),
    ("bad_defaults.py", "mutable-default"),
])
def test_cli_red_on_seeded_fixture(fixture, rule):
    p = _cli(_fixture(fixture))
    assert p.returncode == 1, p.stdout + p.stderr
    assert f"[{rule}]" in p.stdout, (rule, p.stdout)


def test_self_join_guard_shape_passes():
    """The PR 5 FIX shape — a current_thread() identity guard around
    the join — must NOT fire self-join (regression teeth for the
    guard detection; serve/batcher.py relies on it)."""
    src = '''
import threading


class Flusher:
    def __init__(self):
        self._worker = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        pass

    def close(self):
        if threading.current_thread() is not self._worker:
            self._worker.join()
'''
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "guarded.py")
        open(path, "w").write(src)
        fs = locks.check_paths([(path, "guarded.py")])
    assert not [f for f in fs if f.rule == "self-join"], fs


def test_lock_auditor_sees_the_real_graph():
    """Non-vacuity: the auditor discovers the serve/resilience/obs
    lock population (including the batcher Condition aliased to its
    Lock) and the service-lock -> cache-lock edge service._batcher_for
    actually takes."""
    files = default_scan_files(ROOT)
    pairs = [(p, rel(p, ROOT)) for p in files
             if locks.in_audit_scope(rel(p, ROOT))]
    a = locks.Auditor(pairs)
    a.run()
    all_locks = set()
    for fm in a.files:
        all_locks |= set(fm.locks.values())
    assert "serve.batcher.MicroBatcher._lock" in all_locks
    assert "serve.service.SolveService._lock" in all_locks
    # Condition(self._lock) aliases onto the underlying lock
    bat = [fm for fm in a.files if fm.mod == "serve.batcher"][0]
    assert bat.canon("serve.batcher.MicroBatcher._cond") \
        == "serve.batcher.MicroBatcher._lock"
    assert ("serve.service.SolveService._lock",
            "serve.factor_cache.FactorCache._lock") in a.edges


def test_lock_order_annotation_adds_edge():
    """`# slulint: lock-order A -> B` declares edges inference can't
    see — two annotations closing a cycle must fail."""
    src = '''
import threading

_a = threading.Lock()
# slulint: lock-order m.one -> m.two
# slulint: lock-order m.two -> m.one
'''
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ann.py")
        open(path, "w").write(src)
        fs = locks.check_paths([(path, "ann.py")])
    assert [f for f in fs if f.rule == "lock-cycle"], fs


def test_ok_annotation_suppresses():
    """`# slulint: ok <rule>` on the line (or above) suppresses."""
    src = ("import os\n\n\n"
           "def f():\n"
           "    # slulint: ok env-read -- fixture\n"
           "    return os.environ.get('SLU_X')\n")
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "supp.py")
        open(path, "w").write(src)
        fs = rules.check_file(path, "superlu_dist_tpu/supp.py")
    assert not [f for f in fs if f.rule == "env-read"], fs


# -- baseline ratchet --------------------------------------------------

def test_baseline_update_roundtrip(tmp_path):
    """A finding fails the gate, --update adopts it (with empty
    justification preserved-able), the gate then passes, and fixing
    the finding reports the baseline entry stale."""
    base = tmp_path / "BL.json"
    fix = _fixture("bad_defaults.py")
    p = _cli("--baseline", str(base), fix)
    assert p.returncode == 1
    p = _cli("--baseline", str(base), "--update", fix)
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(base.read_text())
    assert doc["version"] == 1 and len(doc["entries"]) == 1
    fp = next(iter(doc["entries"]))
    assert fp.startswith("mutable-default::")
    # justification text survives a re-update
    doc["entries"][fp] = "seeded fixture, tolerated for the roundtrip"
    base.write_text(json.dumps(doc))
    p = _cli("--baseline", str(base), fix)
    assert p.returncode == 0, p.stdout
    assert "1 baselined" in p.stdout
    p = _cli("--baseline", str(base), "--update", fix)
    assert json.loads(base.read_text())["entries"][fp] \
        == "seeded fixture, tolerated for the roundtrip"
    # a clean file against the same baseline: stale entry reported,
    # rc stays 0 (the ratchet tightens via --update, never blocks)
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    p = _cli("--baseline", str(base), str(clean))
    assert p.returncode == 0
    assert "stale" in p.stdout


def test_partial_update_carries_out_of_scope_entries(tmp_path):
    """A `--update` on an explicit path set must NOT prune baseline
    entries belonging to files (or passes) it did not scan — the
    review-found pruning bug: a --no-contracts --update would have
    silently deleted justified hlo-contract entries."""
    base = tmp_path / "BL.json"
    doc = {"version": 1, "updated": None, "entries": {
        "hlo-contract::superlu_dist_tpu/ops/trisolve.py::x:no_scatter":
            "tolerated: justified elsewhere",
        "mutable-default::tests/fixtures/slulint/bad_defaults.py"
        "::accumulate:list literal": ""}}
    base.write_text(json.dumps(doc))
    # update over ONLY the clean file: the fixture entry (out of the
    # scanned path set) and the contract entry must both survive
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    p = _cli("--baseline", str(base), "--update", str(clean))
    assert p.returncode == 0, p.stdout + p.stderr
    kept = json.loads(base.read_text())["entries"]
    assert len(kept) == 2 and any(
        k.startswith("hlo-contract::") for k in kept), kept
    assert kept["hlo-contract::superlu_dist_tpu/ops/trisolve.py"
                "::x:no_scatter"] == "tolerated: justified elsewhere"


def test_multi_item_with_draws_acquisition_edges():
    """`with self._a, self._b:` acquires in item order — a reversed
    nested acquisition elsewhere must close a detectable cycle (the
    review-found inference gap)."""
    src = '''
import threading


class Pool:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def both(self):
        with self._a, self._b:
            return 1

    def rev(self):
        with self._b:
            with self._a:
                return 0
'''
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "multi.py")
        open(path, "w").write(src)
        fs = locks.check_paths([(path, "multi.py")])
    assert [f for f in fs if f.rule == "lock-cycle"], fs


def test_join_under_lock_ignores_str_and_path_joins():
    """str.join / os.path.join under a held lock are not thread
    joins (the review-found false positive that would abort the fire
    plan); a thread-like receiver still fires."""
    src = '''
import os
import threading


class Store:
    def __init__(self):
        self._lock = threading.Lock()

    def path_of(self, key):
        with self._lock:
            name = "-".join(["a", key])
            return os.path.join("/tmp", name)

    def stop(self, worker_thread):
        with self._lock:
            worker_thread.join()
'''
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "joins.py")
        open(path, "w").write(src)
        fs = [f for f in locks.check_paths([(path, "joins.py")])
              if f.rule == "join-under-lock"]
    assert len(fs) == 1 and "worker_thread" in fs[0].msg, fs


def test_corrupt_baseline_is_a_clean_error(tmp_path):
    base = tmp_path / "BL.json"
    base.write_text("{not json")
    p = _cli("--baseline", str(base), _fixture("bad_defaults.py"))
    assert p.returncode not in (0, 1) or "corrupt" in (p.stderr
                                                       + p.stdout)


# -- HLO contract registry --------------------------------------------

def test_registry_covers_the_acceptance_invariants():
    """The three invariants formerly pinned by ad-hoc test regexes
    are registry entries: trisolve zero-scatter, residual
    zero-scatter, df64 zero-f64."""
    names = {e["name"]: e for e in contracts.iter_contracts()}
    assert "no_scatter" in names["trisolve.packed_solve"]["contracts"]
    assert "no_scatter" in names["trisolve.pack"]["contracts"]
    assert "no_scatter" in names["residual.ell_spmv"]["contracts"]
    assert "no_f64" in names["df64.fused_core"]["contracts"]
    assert "check" in names["df64.eft_mul"]          # EFT probe
    # every declared phase names a real watch_jit wrapper
    phases = contracts.registered_phases(ROOT)
    for e in names.values():
        if e.get("phase"):
            assert e["phase"] in phases, e["name"]


def test_contract_red_on_scatter_toy():
    """A scatter reintroduced into a trisolve-shaped toy jit fails
    no_scatter through the same check machinery."""
    import jax
    import jax.numpy as jnp

    def build():
        fn = jax.jit(lambda x, i, v: x.at[i].add(v))
        return fn, (jnp.zeros((16, 2)),
                    jnp.arange(4), jnp.ones((4, 2))), {}

    fs = contracts.check_entry({
        "name": "toy.scatter", "contracts": ("no_scatter",),
        "build": build})
    assert fs and "no_scatter" in fs[0].msg, fs


def test_contract_red_on_f64_in_df64_build():
    """An f64 op inside a df64-claimed program fails no_f64."""
    import jax
    import jax.numpy as jnp

    def build():
        fn = jax.jit(lambda h, l: (h.astype(jnp.float64)
                                   + l.astype(jnp.float64)))
        return fn, (jnp.zeros(8, jnp.float32),
                    jnp.zeros(8, jnp.float32)), {}

    fs = contracts.check_entry({
        "name": "toy.f64", "contracts": ("no_f64",), "build": build})
    assert fs and "no_f64" in fs[0].msg, fs


def test_contract_build_failure_is_a_finding_not_a_crash():
    def build():
        raise ValueError("boom")
    fs = contracts.check_entry({
        "name": "toy.broken", "contracts": ("no_scatter",),
        "build": build})
    assert fs and "build/lower failed" in fs[0].msg


def test_predicates_are_the_one_definition():
    """The text predicates the migrated tests import behave as the
    former inline regexes did — incl. the (?<!d)f64 guard that lets
    'df64' metadata NAMES through."""
    assert not contracts.has_f64("module @df64_refine_thing")
    assert contracts.has_f64("%0 = f64[4] parameter(0)")
    assert contracts.scatter_count("a Scatter op and a scatter") == 2
    assert contracts.donation_present("tf.aliasing_output = 0")
    assert not contracts.donation_present("plain module")


# -- fingerprints ------------------------------------------------------

def test_fingerprints_are_line_stable():
    f1 = Finding("r", "p.py", 10, "msg", detail="sym")
    f2 = Finding("r", "p.py", 99, "msg", detail="sym")
    assert f1.fingerprint == f2.fingerprint


# -- the ServeError taxonomy audit ------------------------------------

def _taxonomy_tree(tmp_path, errors_src, loadgen_src, service_src):
    serve = tmp_path / "superlu_dist_tpu" / "serve"
    serve.mkdir(parents=True)
    (serve / "errors.py").write_text(errors_src)
    (serve / "loadgen.py").write_text(loadgen_src)
    (serve / "service.py").write_text(service_src)
    return str(tmp_path)


_TAX_ERRORS = '''
class ServeError(Exception):
    pass

class ServeRejected(ServeError):
    pass

class TenantThrottled(ServeRejected):
    pass

class Orphaned(ServeError):
    pass
'''

_TAX_LOADGEN = '''
from .errors import Orphaned, ServeError, ServeRejected, \\
    TenantThrottled

def _status_of_solve(do_solve):
    try:
        return do_solve(), None
    except TenantThrottled:
        return "shed", None
    except ServeRejected:
        return "rejected", None
    except Orphaned:
        return "orphaned", None
    except ServeError:
        return "serve_error", None
'''

_TAX_SERVICE = '''
from .errors import Orphaned, ServeError, ServeRejected, \\
    TenantThrottled

def _outcome_of(e):
    for cls, name in ((TenantThrottled, "shed"),
                      (ServeRejected, "rejected"),
                      (Orphaned, "orphaned"),
                      (ServeError, "serve_error")):
        if isinstance(e, cls):
            return name
    return "ok"
'''


def test_taxonomy_audit_green_on_head():
    """Every ServeError subclass on HEAD is named in BOTH status
    ledgers — the pin that makes 'new error class, forgot the
    ledger' a lint failure instead of silent serve_error drift."""
    from tools.slulint.rules.taxonomy import taxonomy_audit
    assert taxonomy_audit(ROOT) == []


def test_taxonomy_audit_green_on_fully_mapped_tree(tmp_path):
    from tools.slulint.rules.taxonomy import taxonomy_audit
    root = _taxonomy_tree(tmp_path, _TAX_ERRORS, _TAX_LOADGEN,
                          _TAX_SERVICE)
    assert taxonomy_audit(root) == []


def test_taxonomy_audit_red_on_unmapped_subclass(tmp_path):
    """Dropping one subclass from one ledger yields exactly one
    finding naming the class, the ledger, and the subclass's line in
    errors.py — transitive subclasses (TenantThrottled under
    ServeRejected) are still covered."""
    from tools.slulint.rules.taxonomy import taxonomy_audit
    lg = _TAX_LOADGEN.replace("    except Orphaned:\n"
                              "        return \"orphaned\", None\n",
                              "")
    root = _taxonomy_tree(tmp_path, _TAX_ERRORS, lg, _TAX_SERVICE)
    fs = taxonomy_audit(root)
    assert len(fs) == 1
    (f,) = fs
    assert f.rule == "untyped-status"
    assert "Orphaned" in f.msg and "_status_of_solve" in f.msg
    assert f.path == "superlu_dist_tpu/serve/errors.py"
    assert f.line > 0
    # the fingerprint detail is class+ledger: a rename shows up as a
    # NEW finding, not a silently-matching baseline entry
    assert f.detail == "Orphaned:_status_of_solve"


def test_taxonomy_audit_red_on_missing_ledger(tmp_path):
    from tools.slulint.rules.taxonomy import taxonomy_audit
    root = _taxonomy_tree(tmp_path, _TAX_ERRORS, "x = 1\n",
                          _TAX_SERVICE)
    fs = taxonomy_audit(root)
    assert any("not found" in f.msg for f in fs)
