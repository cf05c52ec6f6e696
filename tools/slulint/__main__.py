"""slulint CLI.  See the package docstring for the pass catalog.

    python -m tools.slulint                  # full gate (rc 1 on new findings)
    python -m tools.slulint --no-contracts   # AST + locks only (fast, no jax)
    python -m tools.slulint --contracts-only # HLO registry only
    python -m tools.slulint FILE...          # lint specific files (fixtures)
    python -m tools.slulint --update         # re-baseline (keeps justifications)
    python -m tools.slulint --json           # machine-readable findings

When ruff is installed, the full gate additionally runs `ruff check`
with the committed ruff.toml; this container doesn't bake it, so the
native unused-import rule carries the hygiene floor either way.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from . import Finding, default_scan_files, rel, repo_root
from . import baseline as bl
from . import locks, rules


def _run_ruff(root: str) -> tuple[list[Finding], bool]:
    """(findings, ran): `ruff check` against the committed config —
    only when the tool exists (the gate must not require it)."""
    exe = shutil.which("ruff")
    if exe is None:
        return [], False
    try:
        proc = subprocess.run(
            [exe, "check", "--output-format", "json", "--exit-zero",
             "superlu_dist_tpu", "tools"],
            cwd=root, capture_output=True, text=True, timeout=120)
        items = json.loads(proc.stdout or "[]")
    except (OSError, ValueError, subprocess.TimeoutExpired):
        return [], False
    out = []
    for it in items:
        path = rel(it.get("filename", "?"), root)
        code = it.get("code") or "ruff"
        out.append(Finding(
            f"ruff-{code}", path,
            int(it.get("location", {}).get("row", 0)),
            it.get("message", ""),
            detail=f"{code}:{it.get('message', '')[:60]}"))
    return out, True


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    root = repo_root()
    do_update = "--update" in argv
    as_json = "--json" in argv
    no_contracts = "--no-contracts" in argv
    contracts_only = "--contracts-only" in argv
    baseline_path = os.path.join(root, bl.BASELINE_NAME)
    if "--baseline" in argv:
        i = argv.index("--baseline")
        baseline_path = argv[i + 1]
        del argv[i:i + 2]
    for flag in ("--update", "--json", "--no-contracts",
                 "--contracts-only"):
        while flag in argv:
            argv.remove(flag)
    explicit_paths = argv

    findings: list[Finding] = []
    scanned_paths: set[str] = set()
    if explicit_paths:
        # explicit-file mode (fixtures, pre-commit): AST rules + lock
        # audit on exactly these files; no flag audit (it is a whole-
        # repo property), no contracts, no ruff
        pairs = []
        for p in explicit_paths:
            ap = os.path.abspath(p)
            if not os.path.exists(ap):
                print(f"slulint: no such file: {p}", file=sys.stderr)
                return 2
            pairs.append((ap, rel(ap, root)))
        scanned_paths = {rp for _, rp in pairs}
        for ap, rp in pairs:
            findings.extend(rules.check_file(ap, rp))
        findings.extend(locks.check_paths(pairs))
    else:
        if not contracts_only:
            files = default_scan_files(root)
            pairs = [(p, rel(p, root)) for p in files]
            for ap, rp in pairs:
                findings.extend(rules.check_file(ap, rp))
            findings.extend(locks.check_paths(
                [(a, r) for a, r in pairs if locks.in_audit_scope(r)]))
            from .rules.envreads import flag_audit
            findings.extend(flag_audit(root))
            from .rules.taxonomy import taxonomy_audit
            findings.extend(taxonomy_audit(root))
            ruff_findings, ran = _run_ruff(root)
            findings.extend(ruff_findings)
        if not no_contracts:
            from . import contracts
            findings.extend(contracts.check_all(root))

    entries = bl.load(baseline_path)

    def out_of_scope(fp: str) -> bool:
        """Baseline entries belonging to a pass (or path set) this
        invocation did NOT run: a partial `--update` must carry them
        forward untouched, not silently prune them, and the stale
        report must not name them."""
        rule, _, rest = fp.partition("::")
        path = rest.partition("::")[0]
        if explicit_paths:
            return path not in scanned_paths
        if no_contracts and rule == "hlo-contract":
            return True
        if contracts_only and rule != "hlo-contract":
            return True
        return False

    if do_update:
        import time
        carried = {fp: j for fp, j in entries.items()
                   if out_of_scope(fp)}
        bl.save(baseline_path, findings, old_entries=entries,
                extra_entries=carried,
                ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
        print(f"slulint: baseline rewritten -> {baseline_path} "
              f"({len(findings)} entries"
              + (f" + {len(carried)} carried from skipped passes"
                 if carried else "") + ")")
        return 0
    new, stale = bl.gate(findings, entries)
    stale = [fp for fp in stale if not out_of_scope(fp)]

    if as_json:
        print(json.dumps({
            "passed": not new,
            "findings": [vars(f) for f in findings],
            "new": [vars(f) for f in new],
            "stale_baseline": stale}, indent=1))
        return 0 if not new else 1

    for f in new:
        print(f.format())
    for fp in stale:
        print(f"[stale-baseline] {fp} — no longer occurs; prune with "
              "--update")
    known = len(findings) - len(new)
    print(f"slulint: {len(new)} new finding(s), {known} baselined, "
          f"{len(stale)} stale baseline entr"
          f"{'y' if len(stale) == 1 else 'ies'}")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
