"""`env-read`: direct environment reads outside the flags.py gateway.

Package code must read environment knobs through the
superlu_dist_tpu.flags accessors (env_opt/env_str/env_int/env_float),
which refuse undocumented names — a direct `os.environ.get` both
bypasses that refusal and scatters the knob surface the FLAGS table
exists to centralize.  Flagged READ forms: `os.getenv(...)`,
`os.environ.get(...)`, `os.environ[...]` loads, and the same through
`from os import environ`.  Writes (`os.environ[k] = v`) and
membership tests (`k in os.environ`) are not reads and stay legal —
the bootstrap sites (utils/platform.py amalg defaults, utils/compat.py
XLA_FLAGS rewrite) need them.
"""

from __future__ import annotations

import ast

from .. import Finding

RULE = "env-read"


def _is_environ(node: ast.AST) -> bool:
    """`os.environ` or a bare `environ` (from os import environ)."""
    if isinstance(node, ast.Attribute) and node.attr == "environ" \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "os":
        return True
    return isinstance(node, ast.Name) and node.id == "environ"


def check(tree, src, path, ann):
    out = []

    def emit(node, what):
        out.append(Finding(
            RULE, path, node.lineno,
            f"direct environment read ({what}) — route through the "
            "superlu_dist_tpu.flags accessors",
            detail=what))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            # os.getenv(...)
            if isinstance(fn, ast.Attribute) and fn.attr == "getenv" \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id == "os":
                name = _const_arg(node)
                emit(node, f"os.getenv({name})")
            # os.environ.get(...)
            elif isinstance(fn, ast.Attribute) and fn.attr == "get" \
                    and _is_environ(fn.value):
                name = _const_arg(node)
                emit(node, f"os.environ.get({name})")
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and _is_environ(node.value):
            name = ""
            if isinstance(node.slice, ast.Constant):
                name = repr(node.slice.value)
            emit(node, f"os.environ[{name}]")
    return out


def _const_arg(call: ast.Call) -> str:
    if call.args and isinstance(call.args[0], ast.Constant):
        return repr(call.args[0].value)
    return "..."


# --------------------------------------------------------------------
# the whole-repo SLU_* documentation audit
# --------------------------------------------------------------------

def flag_audit(root: str) -> list[Finding]:
    """`undocumented-flag` / `stale-flag`: every SLU_* token in the
    package and tools/ must be documented in
    superlu_dist_tpu/flags.py FLAGS (or listed in NON_FLAG_TOKENS),
    and FLAGS must carry no entry nothing reads — the audit
    tests/test_flags.py ran as a grep since PR 2, now a slulint rule
    (the test is a thin wrapper over this function)."""
    import importlib.util
    import os
    import re as _re

    from .. import default_scan_files, rel
    spec = importlib.util.spec_from_file_location(
        "_slu_flags", os.path.join(root, "superlu_dist_tpu",
                                   "flags.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)        # flags.py imports only os
    token = _re.compile(r"SLU_[A-Z_0-9]*")
    found: dict[str, str] = {}
    for path in default_scan_files(root):
        rp = rel(path, root)
        if os.path.basename(path) == "flags.py":
            continue                    # the registry names every flag
        for tok in token.findall(open(path).read()):
            found.setdefault(tok, rp)
    out = []
    for tok, rp in sorted(found.items()):
        if tok not in mod.FLAGS and tok not in mod.NON_FLAG_TOKENS:
            out.append(Finding(
                "undocumented-flag", rp, 0,
                f"{tok} is read but not documented in "
                "superlu_dist_tpu/flags.py FLAGS",
                detail=tok))
    for flag in sorted(set(mod.FLAGS) - set(found)):
        out.append(Finding(
            "stale-flag", "superlu_dist_tpu/flags.py", 0,
            f"FLAGS documents {flag} but no source file reads it",
            detail=flag))
    return out
