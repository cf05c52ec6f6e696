"""slulint — project-native static analysis for superlu_dist_tpu.

The codebase's load-bearing invariants were enforced by scattered
ad-hoc means: HLO regexes duplicated across tests (zero scatter ops in
the trisolve/residual programs, zero f64 in df64 builds), a grep in
tests/test_flags.py for undocumented SLU_* reads, and bug classes that
static analysis would have caught before measurement did — the PR 5
flusher self-join deadlock, the PR 7 static_argnames-kwarg
slow-dispatch tax, the PR 4 fp-contraction EFT hazard.  slulint turns
each of those into a checked contract:

  * contracts  — a declarative HLO contract registry (contracts.py):
    per-module HLO_CONTRACTS declarations next to the code they
    protect map each whole-phase jit to checks (`no_scatter`,
    `no_f64`, `no_host_callback`, `donation_honored`, custom semantic
    probes like EFT-survival), verified by lowering at representative
    signatures.
  * rules      — AST lints (rules/): env reads outside flags.py,
    host-only calls inside traced code, static_argnames kwarg calls,
    untyped raises in serve/resilience, bare except, mutable default
    args, unused imports, and the SLU_* flag-documentation audit.
  * locks      — a lock-order auditor (locks.py) over serve/,
    resilience/, obs/ and utils/warmup.py: lock-acquisition graph
    (inferred + `# slulint: lock-order A -> B` annotations), cycle
    detection, joins of own worker threads without a current_thread
    guard (the PR 5 deadlock class), joins while holding a lock.

Violations ratchet against the committed SLULINT_BASELINE.json
(`--update` refreshes it, preserving per-entry justifications).  CLI:

    python -m tools.slulint              # full gate; rc != 0 on new findings
    python -m tools.slulint --no-contracts   # fast: AST + locks only
    python -m tools.slulint path.py ...  # lint specific files
    python -m tools.slulint --update     # re-baseline

Annotation syntax (DESIGN.md §17): `# slulint: ok <rule> [-- reason]`
on the offending line (or the line above) suppresses one rule there;
`# slulint: lock-order A -> B` declares a lock-order edge inference
cannot see.
"""

from __future__ import annotations

import dataclasses
import os
import re


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation.  `detail` is the stable leg of the fingerprint —
    it must not contain line numbers, so a baseline entry survives
    unrelated edits above it."""

    rule: str
    path: str          # repo-relative
    line: int
    msg: str
    detail: str = ""

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}::{self.path}::{self.detail or self.msg}"

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def rel(path: str, root: str | None = None) -> str:
    return os.path.relpath(os.path.abspath(path),
                           root or repo_root()).replace(os.sep, "/")


def default_scan_files(root: str | None = None) -> list[str]:
    """The gate's scan set: the package and tools/ — the
    same universe tests/test_flags.py always audited.  tests/ are
    deliberately out (fixtures under tests/fixtures/slulint SEED
    violations)."""
    root = root or repo_root()
    out = []
    for top in ("superlu_dist_tpu", "tools"):
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    out.append(os.path.join(dirpath, f))
    return out


_ANN = re.compile(r"#\s*slulint:\s*(.+?)\s*$")
_ANN_OK = re.compile(r"ok\s+([a-z0-9-]+)")
_ANN_EDGE = re.compile(r"lock-order\s+(\S+)\s*->\s*(\S+)")


class Annotations:
    """Per-file `# slulint:` comment directives: `ok <rule>`
    suppressions (keyed by line) and declared lock-order edges."""

    def __init__(self, src: str):
        self.ok: dict[int, set[str]] = {}
        self.edges: list[tuple[str, str, int]] = []
        for i, ln in enumerate(src.splitlines(), start=1):
            m = _ANN.search(ln)
            if not m:
                continue
            body = m.group(1)
            mo = _ANN_OK.search(body)
            if mo:
                self.ok.setdefault(i, set()).add(mo.group(1))
            me = _ANN_EDGE.search(body)
            if me:
                self.edges.append((me.group(1), me.group(2), i))

    def suppressed(self, rule: str, line: int) -> bool:
        """An `ok` annotation suppresses on its own line or the line
        directly below it (annotation-above style)."""
        for ln in (line, line - 1):
            if rule in self.ok.get(ln, ()):
                return True
        return False
