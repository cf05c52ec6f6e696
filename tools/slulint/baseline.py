"""Baseline ratchet: committed findings that are tolerated, for now.

A finding either gets FIXED, or it ships in
SLULINT_BASELINE.json with a per-entry justification, reviewed next
to the code that earns it.  The gate fails on any finding NOT in the
baseline; baseline entries that no longer occur are reported as
`stale` (prune them with --update — the ratchet only tightens).

File format:

    {"version": 1,
     "updated": "...",
     "entries": {"<rule>::<path>::<detail>": "justification", ...}}

Fingerprints carry no line numbers, so entries survive unrelated
edits in the same file.
"""

from __future__ import annotations

import json
import os

from . import Finding

BASELINE_NAME = "SLULINT_BASELINE.json"


def load(path: str) -> dict:
    """entries dict (fingerprint -> justification); {} when absent."""
    try:
        doc = json.load(open(path))
    except OSError:
        return {}
    except ValueError as e:
        raise SystemExit(f"slulint: corrupt baseline {path}: {e}")
    entries = doc.get("entries", {})
    if not isinstance(entries, dict):
        raise SystemExit(f"slulint: malformed baseline {path}: "
                         "'entries' must be an object")
    return entries


def save(path: str, findings: list[Finding],
         old_entries: dict | None = None,
         extra_entries: dict | None = None, ts: str | None = None):
    """Rewrite the baseline from current findings, preserving the
    justification text of entries that survive.  `extra_entries` are
    carried forward verbatim — the out-of-scope entries of a partial
    run (--no-contracts / --contracts-only / explicit paths), which a
    partial --update must not prune."""
    old_entries = old_entries or {}
    entries = dict(extra_entries or {})
    for f in sorted(findings, key=lambda f: f.fingerprint):
        entries[f.fingerprint] = old_entries.get(f.fingerprint, "")
    doc = {"version": 1, "updated": ts, "entries": entries}
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return entries


def gate(findings: list[Finding],
         entries: dict) -> tuple[list[Finding], list[str]]:
    """(new findings not covered by the baseline, stale baseline
    fingerprints no current finding matches)."""
    current = {f.fingerprint for f in findings}
    new = [f for f in findings if f.fingerprint not in entries]
    stale = sorted(fp for fp in entries if fp not in current)
    return new, stale
