"""Lock-order auditor: acquisition graph, cycles, hostile joins.

The threaded surface of this codebase — serve/'s flusher threads and
single-flight factor cache, resilience/'s breaker and store, obs/'s
registries, utils/warmup.py's parallel compile pool — has already
produced one real deadlock (PR 5: MicroBatcher.close() self-joining
the flusher from its own future-callback thread) and holds a growing
set of ordering conventions the code keeps only by discipline.  This
pass makes the discipline checkable:

  * lock-acquisition GRAPH — locks are `threading.Lock/RLock/
    Condition` objects assigned to `self.<attr>` or module globals;
    an edge A -> B means code acquires B while holding A.  Inference
    is lexical `with` nesting plus ONE level of intra-module call
    resolution (`self.m()` to the same class, `f()` to the same
    module, `self.<attr>.m()` through constructor-assigned attribute
    types declared in the audited set) — where inference falls short,
    a `# slulint: lock-order mod.Class._a -> mod.Class._b` annotation
    declares the edge.  Rule `lock-cycle` fails on any strongly
    connected component.
  * `self-join` — `self.<thread-attr>.join()` where the attr holds a
    `threading.Thread`, in a method WITHOUT a
    `threading.current_thread() is [not] self.<attr>` guard: exactly
    the PR 5 class (close() invoked from the thread's own callback).
  * `join-under-lock` — any `.join()` while lexically holding a lock:
    the joined thread typically needs that lock to finish.

Lock identities are `module.Class.attr` (or `module.name` for
globals); `Condition(self._lock)` aliases to its underlying lock.
"""

from __future__ import annotations

import ast
import os
import re

from . import Annotations, Finding

RULE_CYCLE = "lock-cycle"
RULE_SELF_JOIN = "self-join"
RULE_JOIN_LOCK = "join-under-lock"

# package files in the audited set (repo-relative prefixes/paths)
AUDIT_PREFIXES = ("superlu_dist_tpu/serve/",
                  "superlu_dist_tpu/resilience/",
                  "superlu_dist_tpu/obs/",
                  "superlu_dist_tpu/fleet/",
                  "superlu_dist_tpu/stream/")
AUDIT_FILES = ("superlu_dist_tpu/utils/warmup.py",)


def in_audit_scope(path_rel: str) -> bool:
    return (path_rel.startswith(AUDIT_PREFIXES)
            or path_rel in AUDIT_FILES)


def _modname(path_rel: str) -> str:
    p = path_rel
    for pre in ("superlu_dist_tpu/",):
        if p.startswith(pre):
            p = p[len(pre):]
    return p[:-3].replace("/", ".") if p.endswith(".py") else p


def _dotted(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return ()


_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
               "BoundedSemaphore"}


def _lock_ctor(call) -> str | None:
    if not isinstance(call, ast.Call):
        return None
    d = _dotted(call.func)
    if d and d[-1] in _LOCK_CTORS \
            and (len(d) == 1 or d[0] == "threading"):
        return d[-1]
    return None


def _thread_ctor(call) -> bool:
    if not isinstance(call, ast.Call):
        return False
    d = _dotted(call.func)
    return bool(d) and d[-1] == "Thread"


class _FileModel:
    """Parsed facts of one audited file."""

    def __init__(self, path_abs: str, path_rel: str):
        self.path = path_rel
        self.mod = _modname(path_rel)
        self.src = open(path_abs).read()
        self.tree = ast.parse(self.src)
        self.ann = Annotations(self.src)
        # (class or None, attr/name) -> canonical lock id
        self.locks: dict[tuple, str] = {}
        # alias resolution: lock id -> canonical id (Condition(_lock))
        self.alias: dict[str, str] = {}
        self.thread_attrs: dict[str, set] = {}      # class -> attrs
        # class -> {attr -> ClassName} from `self.x = ClassName(...)`
        self.attr_types: dict[str, dict] = {}
        self.classes: dict[str, ast.ClassDef] = {}
        self.functions: dict[tuple, ast.AST] = {}   # (cls|None, name)
        self._collect()

    def _collect(self):
        for node in self.tree.body:
            if isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        self.functions[(node.name, sub.name)] = sub
                        self._collect_assigns(sub, node.name)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                self.functions[(None, node.name)] = node
            elif isinstance(node, ast.Assign):
                kind = _lock_ctor(node.value)
                if kind:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            lid = f"{self.mod}.{tgt.id}"
                            self.locks[(None, tgt.id)] = lid

    def _collect_assigns(self, fn, cls: str):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                kind = _lock_ctor(node.value)
                if kind:
                    lid = f"{self.mod}.{cls}.{tgt.attr}"
                    self.locks[(cls, tgt.attr)] = lid
                    # Condition(self._lock) aliases to the wrapped lock
                    if kind == "Condition" and node.value.args:
                        inner = node.value.args[0]
                        if isinstance(inner, ast.Attribute) \
                                and isinstance(inner.value, ast.Name) \
                                and inner.value.id == "self":
                            self.alias[lid] = \
                                f"{self.mod}.{cls}.{inner.attr}"
                elif _thread_ctor(node.value):
                    self.thread_attrs.setdefault(cls, set()).add(
                        tgt.attr)
                elif isinstance(node.value, ast.Call):
                    d = _dotted(node.value.func)
                    if d:
                        self.attr_types.setdefault(cls, {})[tgt.attr] \
                            = d[-1]

    def canon(self, lid: str) -> str:
        return self.alias.get(lid, lid)


def _walk_no_nested_defs(fn):
    """ast.walk over a function body that does NOT descend into
    nested function definitions — a closure's locks are acquired when
    the callback RUNS, not when its def executes."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


class Auditor:
    """Cross-file lock analysis over a set of _FileModels."""

    def __init__(self, paths: list[tuple[str, str]]):
        self.files = [_FileModel(a, r) for a, r in paths]
        # ClassName -> (model, ClassDef) across the audited set
        self.class_index: dict[str, tuple] = {}
        for fm in self.files:
            for cname, cdef in fm.classes.items():
                self.class_index.setdefault(cname, (fm, cdef))
        self.edges: dict[tuple[str, str], tuple[str, int]] = {}
        self.findings: list[Finding] = []
        self._acq_memo: dict = {}

    # -- lock resolution ----------------------------------------------

    def _resolve_lock(self, fm: _FileModel, cls, expr) -> str | None:
        """Lock id of a `with` context expression, or None."""
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self" and cls is not None:
            lid = fm.locks.get((cls, expr.attr))
            return fm.canon(lid) if lid else None
        if isinstance(expr, ast.Name):
            lid = fm.locks.get((None, expr.id))
            return fm.canon(lid) if lid else None
        return None

    # -- transitive acquisition sets ----------------------------------

    def acquired_locks(self, fm: _FileModel, cls, fname,
                       _stack=()) -> set:
        """Locks a function may acquire, transitively through
        intra-module / attribute-typed calls."""
        key = (fm.mod, cls, fname)
        if key in self._acq_memo:
            return self._acq_memo[key]
        if key in _stack:
            return set()
        fn = fm.functions.get((cls, fname)) \
            or fm.functions.get((None, fname))
        if fn is None:
            return set()
        out: set = set()
        use_cls = cls if (cls, fname) in fm.functions else None
        for node in _walk_no_nested_defs(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    lid = self._resolve_lock(fm, use_cls,
                                             item.context_expr)
                    if lid:
                        out.add(lid)
            elif isinstance(node, ast.Call):
                for tgt in self._callees(fm, use_cls, node):
                    out |= self.acquired_locks(
                        tgt[0], tgt[1], tgt[2], _stack + (key,))
        self._acq_memo[key] = out
        return out

    def _callees(self, fm: _FileModel, cls, call: ast.Call):
        """Resolvable callees of a call node: (model, cls, fname)."""
        f = call.func
        out = []
        if isinstance(f, ast.Name):
            if (None, f.id) in fm.functions:
                out.append((fm, None, f.id))
        elif isinstance(f, ast.Attribute):
            base = f.value
            if isinstance(base, ast.Name) and base.id == "self" \
                    and cls is not None:
                if (cls, f.attr) in fm.functions:
                    out.append((fm, cls, f.attr))
            elif isinstance(base, ast.Attribute) \
                    and isinstance(base.value, ast.Name) \
                    and base.value.id == "self" and cls is not None:
                # self.<attr>.m() through the constructor-declared
                # attribute type (self.metrics = Metrics(...))
                tname = fm.attr_types.get(cls, {}).get(base.attr)
                hit = self.class_index.get(tname or "")
                if hit and (tname, f.attr) in hit[0].functions:
                    out.append((hit[0], tname, f.attr))
        return out

    # -- per-function walk --------------------------------------------

    def _walk_fn(self, fm: _FileModel, cls, fn):
        nested: list = []
        for stmt in fn.body:
            self._visit(fm, cls, fn, stmt, [], nested)
        # nested defs are callbacks/closures: their bodies run later,
        # not under the lexically-enclosing lock — audit each as an
        # independent function with an empty held set
        for sub in nested:
            self._walk_fn(fm, cls, sub)

    def _visit(self, fm, cls, fn, node, held, nested):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            nested.append(node)
            return
        if isinstance(node, ast.Lambda):
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            new = []
            for item in node.items:
                lid = self._resolve_lock(fm, cls, item.context_expr)
                if lid:
                    # `with self._a, self._b:` acquires in item order:
                    # earlier items of the SAME statement are already
                    # held when a later one is taken, so they edge too
                    for h in held + new:
                        self._edge(h, lid, fm.path, node.lineno)
                    new.append(lid)
                else:
                    self._visit(fm, cls, fn, item.context_expr, held,
                                nested)
            for stmt in node.body:
                self._visit(fm, cls, fn, stmt, held + new, nested)
            return
        if isinstance(node, ast.Call):
            self._check_join(fm, cls, fn, node, held)
            if held:
                for tgt in self._callees(fm, cls, node):
                    for lid in self.acquired_locks(tgt[0], tgt[1],
                                                   tgt[2]):
                        for h in held:
                            self._edge(h, lid, fm.path, node.lineno)
        for child in ast.iter_child_nodes(node):
            self._visit(fm, cls, fn, child, held, nested)

    def _edge(self, a: str, b: str, path: str, line: int):
        if a == b:
            return
        self.edges.setdefault((a, b), (path, line))

    # -- joins ---------------------------------------------------------

    def _check_join(self, fm, cls, fn, call: ast.Call, held):
        f = call.func
        if not (isinstance(f, ast.Attribute) and f.attr == "join"):
            return
        tgt = f.value
        # join of a thread stored on self
        if isinstance(tgt, ast.Attribute) \
                and isinstance(tgt.value, ast.Name) \
                and tgt.value.id == "self" and cls is not None \
                and tgt.attr in fm.thread_attrs.get(cls, ()):
            if held:
                self._emit(fm, RULE_JOIN_LOCK, call.lineno,
                           f"self.{tgt.attr}.join() while holding "
                           f"{sorted(held)} — the joined thread may "
                           "need that lock to exit",
                           f"{cls}.{fn.name}:{tgt.attr}")
            if not self._has_identity_guard(fn, tgt.attr):
                self._emit(
                    fm, RULE_SELF_JOIN, call.lineno,
                    f"{cls}.{fn.name} joins self.{tgt.attr} without a "
                    "threading.current_thread() identity guard — "
                    "called from that thread's own callback it "
                    "deadlocks (the PR 5 flusher class)",
                    f"{cls}.{fn.name}:{tgt.attr}")
        elif held and self._is_threadlike(fm, cls, fn, tgt):
            # generic fallback for receivers that LOOK like threads —
            # guarded, because `.join()` is also str.join/os.path.join
            # (store.py does path work adjacent to its lock) and a
            # false positive here fails the lint gate
            d = _dotted(tgt)
            self._emit(fm, RULE_JOIN_LOCK, call.lineno,
                       f"{'.'.join(d) or '<expr>'}.join() while "
                       f"holding {sorted(held)}",
                       f"{getattr(fn, 'name', '?')}:"
                       f"{'.'.join(d) or 'expr'}")

    _THREADLIKE = re.compile(r"(thread|worker|flusher|executor|proc)",
                             re.I)

    def _is_threadlike(self, fm, cls, fn, tgt) -> bool:
        """Does a join receiver plausibly denote a thread?  True for
        a local Name assigned threading.Thread(...) in this function,
        or any name/attr chain whose last leg matches the thread-ish
        vocabulary; str literals, str.join on variables, and
        os.path.join all fail both tests."""
        if isinstance(tgt, ast.Name):
            for node in _walk_no_nested_defs(fn):
                if isinstance(node, ast.Assign) \
                        and _thread_ctor(node.value) \
                        and any(isinstance(t, ast.Name)
                                and t.id == tgt.id
                                for t in node.targets):
                    return True
            return bool(self._THREADLIKE.search(tgt.id))
        d = _dotted(tgt)
        if d and d[0] == "os":          # os.path.join and kin
            return False
        return bool(d) and bool(self._THREADLIKE.search(d[-1]))

    @staticmethod
    def _has_identity_guard(fn, attr: str) -> bool:
        """True when `fn` compares threading.current_thread() against
        self.<attr> anywhere (is / is not / ==) — the PR 5 fix
        shape."""
        for node in ast.walk(fn):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left] + list(node.comparators)
            has_cur = any(
                isinstance(s, ast.Call)
                and _dotted(s.func)[-1:] == ("current_thread",)
                for s in sides)
            has_attr = any(
                isinstance(s, ast.Attribute) and s.attr == attr
                and isinstance(s.value, ast.Name)
                and s.value.id == "self"
                for s in sides)
            if has_cur and has_attr:
                return True
        return False

    def _emit(self, fm: _FileModel, rule, line, msg, detail):
        if fm.ann.suppressed(rule, line):
            return
        self.findings.append(Finding(rule, fm.path, line, msg,
                                     detail=detail))

    # -- driver ---------------------------------------------------------

    def run(self) -> list[Finding]:
        for fm in self.files:
            for (cls, fname), fn in fm.functions.items():
                self._walk_fn(fm, cls, fn)
            for a, b, line in fm.ann.edges:
                self._edge(a, b, fm.path, line)
        self._cycles()
        return self.findings

    def _cycles(self):
        graph: dict[str, set] = {}
        for (a, b) in self.edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())
        for scc in _tarjan(graph):
            if len(scc) > 1 or (len(scc) == 1
                                and scc[0] in graph.get(scc[0], ())):
                cyc = sorted(scc)
                where = self.edges.get(
                    (cyc[0], cyc[1 % len(cyc)])) or ("", 0)
                for (a, b), (path, line) in sorted(self.edges.items()):
                    if a in scc and b in scc:
                        where = (path, line)
                        break
                self.findings.append(Finding(
                    RULE_CYCLE, where[0] or (cyc[0].split(".")[0]),
                    where[1],
                    "lock-order cycle: " + " -> ".join(
                        cyc + [cyc[0]]) + " — a consistent global "
                    "order (or a lock merge) is required",
                    detail="->".join(cyc)))


def _tarjan(graph: dict) -> list[list]:
    index: dict = {}
    low: dict = {}
    on: set = set()
    stack: list = []
    sccs: list = []
    counter = [0]

    def strong(v):
        # iterative Tarjan: the audited graphs are small but
        # recursion limits are not a failure mode worth having
        work = [(v, iter(sorted(graph[v])))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on.add(w)
                    work.append((w, iter(sorted(graph[w]))))
                    advanced = True
                    break
                elif w in on:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                low[work[-1][0]] = min(low[work[-1][0]], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(scc)

    for v in sorted(graph):
        if v not in index:
            strong(v)
    return sccs


def check_paths(paths_abs_rel: list[tuple[str, str]]) -> list[Finding]:
    """Audit the given (abs, rel) python files as one lock universe."""
    usable = []
    for a, r in paths_abs_rel:
        if not os.path.exists(a):
            continue
        usable.append((a, r))
    if not usable:
        return []
    try:
        return Auditor(usable).run()
    except SyntaxError as e:
        return [Finding("syntax-error", "<locks>", 0, str(e),
                        detail=str(e))]
