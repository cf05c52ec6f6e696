"""slulint AST rule registry.

Each rule module exposes `check(tree, src, path, ann) -> [Finding]`
(path repo-relative, `ann` the file's Annotations).  Scoping is by
path and lives here so the catalog below is the one place to read
where each rule applies:

  env-read         superlu_dist_tpu/** except flags.py (the gateway);
                   tools/ are drivers and exempt
  host-call-in-jit everywhere scanned — host-only calls (time.*,
                   np.random, print, open, env reads) inside
                   jit-decorated or traced-closure functions
  static-kwarg     everywhere — static_argnames jits called with
                   those names as keywords (slow-dispatch tax) unless
                   the parameter is keyword-only (an explicit opt-in)
  untyped-raise    serve/ and resilience/ — raising generic builtin
                   exceptions instead of the serve/errors.py taxonomy
                   (precondition builtins ValueError/TypeError/
                   KeyError/NotImplementedError/AssertionError are
                   caller-bug signals and stay legal)
  bare-except      everywhere
  mutable-default  everywhere — list/dict/set defaults in function
                   signatures (pytree-carrying or not: the aliasing
                   bug class is the same)
  unused-import    everywhere except __init__.py re-export surfaces
                   (the pyflakes-class hygiene fallback; ruff runs
                   instead when installed — see __main__)
"""

from __future__ import annotations

import ast

from .. import Annotations, Finding
from . import dispatch, envreads, hygiene, purity, raises


def _in_pkg(path: str) -> bool:
    return path.startswith("superlu_dist_tpu/")


RULESET = (
    # (rule module, scope predicate)
    (envreads, lambda p: (_in_pkg(p) and not p.endswith("/flags.py"))
        or p.startswith("tests/")),
    (purity, lambda p: True),
    (dispatch, lambda p: True),
    (raises, lambda p: True),       # bare-except everywhere;
                                    # untyped-raise self-scopes to
                                    # serve/resilience paths
    (hygiene, lambda p: True),      # unused-import self-skips
                                    # __init__.py re-export surfaces
)


def check_file(path_abs: str, path_rel: str) -> list[Finding]:
    try:
        src = open(path_abs).read()
    except OSError as e:
        return [Finding("io-error", path_rel, 0, str(e), detail=str(e))]
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("syntax-error", path_rel, e.lineno or 0,
                        str(e.msg), detail=str(e.msg))]
    ann = Annotations(src)
    out: list[Finding] = []
    for mod, scope in RULESET:
        if not scope(path_rel):
            continue
        for f in mod.check(tree, src, path_rel, ann):
            if not ann.suppressed(f.rule, f.line):
                out.append(f)
    return out
