"""The one observability registry: every telemetry surface in the
package — phase stats (utils/stats.py), serve metrics
(serve/metrics.py), the compile watcher, the health monitors, the
tracer — registers a named provider here, so ONE `snapshot()` answers
"where did the time go, did XLA recompile, are the numerics drifting"
as a single dict, and `dump_text()` renders the same thing as a flat
Prometheus-style text exposition (wired into `SolveService`).

A provider is any object with a `snapshot() -> dict` method.
Registration is last-wins per name (one live SolveService / one
last-solve Stats is the intended cardinality); `unregister` is
compare-and-remove so a closed service never tears down its
successor's registration.
"""

from __future__ import annotations

import re
import threading


_KEY_RE = re.compile(r"[^a-zA-Z0-9_]+")


class Registry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._providers: dict[str, object] = {}

    def register(self, name: str, provider) -> object:
        """Register (or replace) the provider under `name`."""
        if not hasattr(provider, "snapshot"):
            raise TypeError(
                f"provider for {name!r} has no snapshot() method")
        with self._lock:
            self._providers[name] = provider
        return provider

    def unregister(self, name: str, provider=None) -> None:
        """Remove `name`; with `provider` given, only if it is still
        the registered one (a replaced registration is left alone)."""
        with self._lock:
            cur = self._providers.get(name)
            if cur is None:
                return
            if provider is None or cur is provider:
                del self._providers[name]

    def get(self, name: str):
        with self._lock:
            return self._providers.get(name)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._providers)

    def snapshot(self) -> dict:
        """{provider name: provider.snapshot()} — one JSON-ready view
        of everything registered.  A provider that raises contributes
        an error marker instead of killing the whole snapshot."""
        with self._lock:
            providers = dict(self._providers)
        out = {}
        for name in sorted(providers):
            try:
                out[name] = providers[name].snapshot()
            except Exception as e:  # observability must not throw
                out[name] = {"error": repr(e)}
        return out

    def dump_text(self) -> str:
        """Flat Prometheus-style exposition: one `slu_<path> <value>`
        line per numeric leaf of the snapshot."""
        lines: list[str] = []

        def walk(prefix: str, node) -> None:
            if isinstance(node, dict):
                for k in sorted(node):
                    walk(prefix + "_" + _KEY_RE.sub("_", str(k)),
                         node[k])
            elif isinstance(node, bool):
                lines.append(f"{prefix} {int(node)}")
            elif isinstance(node, (int, float)):
                lines.append(f"{prefix} {node}")

        walk("slu", self.snapshot())
        return "\n".join(lines) + ("\n" if lines else "")


# the process-wide default registry
REGISTRY = Registry()
