"""Versioned telemetry export: the fleet control room's wire (ISSUE 19).

Every process's `obs.snapshot()` (registry.py: compile watch, health,
tracer, flight, SLO, serve metrics, cache, memory watch) becomes a
schema-stamped, versioned artifact other processes can consume:

  * `export_snapshot()` — the JSON form: the registry snapshot
    wrapped in {schema, version, replica, pid, seq, ts}.  `replica`
    is the fleet-unique boot id (obs/flight.replica_id()), the merge
    key obs/aggregate.py joins on.
  * `export_text()` — the Prometheus-style text form
    (registry.dump_text()) under a schema header comment.
  * an `SLU_OBS_EXPORT` listener — a minimal HTTP loop over a unix
    socket ('unix:/path/sock') or TCP ('host:port' / bare port on
    127.0.0.1) serving /snapshot (JSON) and /metrics (text).
  * an `SLU_OBS_EXPORT_JSONL` periodic write-through — one snapshot
    line per SLU_OBS_EXPORT_PERIOD_S beside the durable store, with
    the tracer's self-disabling sink discipline (first I/O error
    turns the sink off; export never throws into serving).

Cost discipline: the request path is NOT hooked — export reads
snapshots on its own threads, so with the flag unset the only cost
anywhere is the one module-global pointer check (`_exporter is
None`).  On, the serve overhead is the registry snapshot each period
plus per-request handling on listener threads.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import socket
import threading
import time

from .. import flags
from . import flight
from .registry import REGISTRY

EXPORT_SCHEMA = "slu.obs.snapshot"
EXPORT_VERSION = 1

# process-wide snapshot sequence: consumers order torn/duplicated
# lines by (replica, seq) without trusting wall clocks
_seq = itertools.count(1)


def export_snapshot() -> dict:
    """The versioned JSON export record.  Drains deferred flight/SLO
    finalizations first (flight.run_drain_hooks) so the snapshot is
    current, exactly like SolveService.obs_snapshot."""
    flight.run_drain_hooks()
    return {
        "schema": EXPORT_SCHEMA,
        "version": EXPORT_VERSION,
        "replica": flight.replica_id(),
        "pid": os.getpid(),
        "seq": next(_seq),
        "ts": time.time(),
        "obs": REGISTRY.snapshot(),
    }


def export_text() -> str:
    """The Prometheus-style text export: the registry text dump under
    a schema header comment carrying the same version/replica stamp
    the JSON form does."""
    flight.run_drain_hooks()
    head = (f"# slu.obs schema={EXPORT_SCHEMA} "
            f"version={EXPORT_VERSION} replica={flight.replica_id()} "
            f"ts={time.time():.3f}\n")
    return head + REGISTRY.dump_text()


def _parse_listen(spec: str):
    """'unix:/path' -> (AF_UNIX, path); 'host:port' / bare port ->
    (AF_INET, (host, port)).  Raises ValueError on a malformed spec
    (a typed precondition error, never served)."""
    if spec.startswith("unix:"):
        path = spec[len("unix:"):]
        if not path:
            raise ValueError(
                f"SLU_OBS_EXPORT unix spec has no path: {spec!r}")
        return socket.AF_UNIX, path
    if spec.isdigit():
        return socket.AF_INET, ("127.0.0.1", int(spec))
    host, sep, port = spec.rpartition(":")
    if not sep or not port.isdigit():
        raise ValueError(
            f"SLU_OBS_EXPORT spec {spec!r} is neither 'unix:/path', "
            "'host:port', nor a bare port")
    return socket.AF_INET, (host or "127.0.0.1", int(port))


class Exporter:
    """One process's export surface: optional listener + optional
    periodic JSONL write-through.  A Registry provider ("export"), so
    the export plane reports on itself."""

    def __init__(self, listen: str | None, jsonl_path: str | None,
                 period_s: float) -> None:
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._requests = 0
        self._request_errors = 0
        self._writes = 0
        self._listen_spec = listen
        self._jsonl_path = jsonl_path
        self._jsonl_error: str | None = None
        self._period_s = max(0.01, float(period_s))
        self._sock: socket.socket | None = None
        self._unix_path: str | None = None
        self.address: str | None = None
        self._threads: list[threading.Thread] = []
        if listen:
            fam, addr = _parse_listen(listen)
            sock = socket.socket(fam, socket.SOCK_STREAM)
            if fam == socket.AF_UNIX:
                try:
                    os.unlink(addr)
                except OSError:
                    pass
                sock.bind(addr)
                self._unix_path = addr
                self.address = f"unix:{addr}"
            else:
                sock.setsockopt(socket.SOL_SOCKET,
                                socket.SO_REUSEADDR, 1)
                sock.bind(addr)
                host, port = sock.getsockname()[:2]
                self.address = f"{host}:{port}"
            sock.listen(16)
            self._sock = sock
            t = threading.Thread(target=self._accept_loop,
                                 name="slu-obs-export-listen",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        if jsonl_path:
            t = threading.Thread(target=self._jsonl_loop,
                                 name="slu-obs-export-jsonl",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    # -- listener ------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                break               # socket closed by close()
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(10.0)
            data = b""
            while b"\r\n" not in data and len(data) < 65536:
                chunk = conn.recv(4096)
                if not chunk:
                    break
                data += chunk
            line = data.split(b"\r\n", 1)[0].decode("latin-1",
                                                    "replace")
            parts = line.split()
            path = parts[1] if len(parts) >= 2 else "/"
            path = path.split("?", 1)[0]
            if path in ("/metrics",):
                body = export_text().encode()
                ctype = b"text/plain; version=0.0.4"
                status = b"200 OK"
            elif path in ("/", "/snapshot"):
                body = json.dumps(export_snapshot(),
                                  default=repr).encode()
                ctype = b"application/json"
                status = b"200 OK"
            else:
                body = b""
                ctype = b"text/plain"
                status = b"404 Not Found"
            conn.sendall(b"HTTP/1.0 " + status
                         + b"\r\nContent-Type: " + ctype
                         + b"\r\nContent-Length: "
                         + str(len(body)).encode()
                         + b"\r\nConnection: close\r\n\r\n" + body)
            with self._lock:
                self._requests += 1
        except Exception:           # noqa: BLE001 — endpoint errors
            with self._lock:        # are counted, never propagated
                self._request_errors += 1
        finally:
            try:
                conn.close()
            except OSError:
                pass

    # -- periodic JSONL write-through ----------------------------------

    def _jsonl_loop(self) -> None:
        while not self._stop.wait(self._period_s):
            if self._jsonl_path is None:
                break               # sink self-disabled: stop ticking
            self.flush_jsonl()

    def flush_jsonl(self) -> None:
        """Write one snapshot line now (the periodic loop's body;
        tests and drills call it to flush deterministically).  Tracer
        sink discipline: any I/O error disables the sink for the
        exporter's lifetime."""
        path = self._jsonl_path
        if path is None:
            return
        try:
            line = json.dumps(export_snapshot(), default=repr)
            with open(path, "a") as f:
                f.write(line + "\n")
            with self._lock:
                self._writes += 1
        except (OSError, ValueError, TypeError) as e:
            self._jsonl_path = None
            self._jsonl_error = repr(e)

    # -- provider ------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "enabled": True,
                "listen": self.address,
                "requests": self._requests,
                "request_errors": self._request_errors,
                "jsonl_path": self._jsonl_path,
                "jsonl_error": self._jsonl_error,
                "writes": self._writes,
                "period_s": self._period_s,
            }

    def close(self) -> None:
        self._stop.set()
        sock = self._sock
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        if self._unix_path:
            try:
                os.unlink(self._unix_path)
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=1.0)
        REGISTRY.unregister("export", self)


# module gate (tracer/flight pattern): ONE pointer to check anywhere
_lock = threading.Lock()
_exporter: Exporter | None = None
_atexit_registered = False


def configure(enabled: bool | None = None, listen: str | None = None,
              jsonl_path: str | None = None,
              period_s: float | None = None) -> Exporter | None:
    """(Re)configure the process exporter from explicit args or the
    environment (None = read the flag).  enabled=False forces off
    regardless of flags — the tests' and A/B arms' off switch."""
    global _exporter, _atexit_registered
    with _lock:
        if listen is None:
            listen = flags.env_opt("SLU_OBS_EXPORT")
            if listen in ("0", ""):
                listen = None
        if jsonl_path is None:
            jsonl_path = flags.env_opt("SLU_OBS_EXPORT_JSONL")
        if period_s is None:
            period_s = flags.env_float("SLU_OBS_EXPORT_PERIOD_S", 5.0)
        if enabled is None:
            enabled = bool(listen or jsonl_path)
        old, _exporter = _exporter, None
    if old is not None:
        old.close()
    if not enabled:
        return None
    exp = Exporter(listen, jsonl_path, period_s)
    with _lock:
        _exporter = exp
        if not _atexit_registered:
            _atexit_registered = True
            atexit.register(_close_at_exit)
    REGISTRY.register("export", exp)
    return exp


def _close_at_exit() -> None:
    global _exporter
    with _lock:
        exp, _exporter = _exporter, None
    if exp is not None:
        exp.close()


def get_exporter() -> Exporter | None:
    return _exporter


def export_enabled() -> bool:
    return _exporter is not None


def fetch(address: str, path: str = "/snapshot",
          timeout_s: float = 5.0):
    """Client side of the endpoint: GET `path` from an exporter
    address ('unix:/path/sock' or 'host:port') and return the parsed
    JSON (for /snapshot) or the text body (for /metrics).  Raises
    OSError/ValueError on connection or schema trouble — callers in
    the gather plane contain it (torn/missing snapshots are counted,
    never a crash)."""
    fam, addr = _parse_listen(address)
    with socket.socket(fam, socket.SOCK_STREAM) as sock:
        sock.settimeout(timeout_s)
        sock.connect(addr)
        sock.sendall(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
    head, sep, body = data.partition(b"\r\n\r\n")
    if not sep:
        raise ValueError(f"export fetch {address}{path}: truncated "
                         "HTTP response")
    status = head.split(b"\r\n", 1)[0]
    if b"200" not in status:
        raise ValueError(f"export fetch {address}{path}: "
                         f"{status.decode('latin-1', 'replace')}")
    if path == "/metrics":
        return body.decode("utf-8", "replace")
    return json.loads(body)


configure()
