"""Where jax's persistent compilation cache lives.

`place_compile_cache` is the ONE place the package, its tools and
its tests set `jax_compilation_cache_dir`.  The rule:

  * `JAX_COMPILATION_CACHE_DIR` set — jax already reads it; nothing
    is set here, so whoever launched the process (an operator, the
    chip tool handing back one output directory) decides where
    compiled programs persist.
  * unset, accelerator — one fixed path inside the checkout
    (`.jax_cache-accel`), never one made from a temporary name, a
    pid or the time: a cache that moves is a cache that never hits.
  * unset, CPU — the host-fingerprinted directory below.

XLA:CPU AOT cache entries embed the COMPILING machine's feature set;
loading them on a host with different CPU features is at best a loud
warning and at worst wrong code (cpu_aot_loader "could lead to
execution errors such as SIGILL").  Workspaces here migrate between
machines, so the CPU cache directory name carries a fingerprint of
the host's CPU flags — each machine type gets its own cache and never
loads another's objects.  It is stable per host.  A TPU executable is
keyed by the DEVICE target and does not depend on host-CPU identity,
which is why accelerator runs share the un-fingerprinted directory.
"""

from __future__ import annotations

import hashlib
import os
import platform
import re

from .. import flags


def ensure_portable_cpu_isa(xla_flags: str) -> str:
    """Append --xla_cpu_max_isa=AVX2 unless an ISA cap is already
    present.  The single definition of the portability guard for
    live-migrating VMs (model-tuned XLA:CPU artifacts executed on a
    different host model produced NaN solves and a SIGSEGV); used by
    tests/conftest.py and the 16-device subprocess test."""
    xla_flags = xla_flags or ""
    if "xla_cpu_max_isa" not in xla_flags:
        xla_flags = (xla_flags + " --xla_cpu_max_isa=AVX2").strip()
    return xla_flags


def _repo_cache_base() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir_for(base: str, accel: bool) -> str:
    """Compilation-cache directory for a run that has already
    resolved where it executes: accelerator runs share one stable
    directory (device-target-keyed entries, host identity
    irrelevant); CPU runs get the host-fingerprinted one."""
    return base + "-accel" if accel else host_cache_dir(base)


def place_compile_cache(path: str | None = None) -> str:
    """Place jax's persistent compilation cache (module docstring)
    and return the directory in force.  `path` overrides the
    in-checkout default for callers that own a directory (the test
    suite's CPU cache); without it
    the default is resolved from the backend jax actually chose, so
    this call initializes the backend."""
    env = flags.env_opt("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if path is None:
        path = cache_dir_for(_repo_cache_base(),
                             accel=jax.default_backend() != "cpu")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
    return path


def host_cache_dir(base: str) -> str:
    """`base` extended with a stable fingerprint of this host's CPU.

    The fingerprint must include the CPU MODEL IDENTITY, not just the
    feature flags: XLA derives extra target features from the detected
    model (e.g. +prefer-no-scatter on some microarchitectures), so two
    hosts with identical cpuinfo flags can still produce mutually
    unloadable (or worse, silently wrong) AOT objects.

    /proc/cpuinfo alone is NOT identity-proof under virtualization:
    this round a VM migration served AOT artifacts with
    +prefer-no-scatter tuning to a host whose real CPUID lacks it
    (NaN solves + a SIGSEGV) while /proc/cpuinfo read the same.  The
    fingerprint therefore leads with RAW CPUID leaves captured by the
    native library (csrc slu_cpuid_words — the same instructions
    LLVM's host detection executes), with /proc/cpuinfo as additional
    salt and the platform strings as last resort."""
    return f"{base}-{_fingerprint()}"


def _fingerprint() -> str:
    parts = []
    try:
        from . import native
        # cpuid_words_fast never triggers the FULL native build (this
        # runs at conftest startup) — it reuses the big .so when
        # current, else builds the sub-second single-TU helper, so the
        # fingerprint is identical across every process of a session
        w = native.cpuid_words_fast()
        if len(w):
            parts.append("cpuid=" + ",".join(hex(int(x)) for x in w))
    except Exception:
        pass
    try:
        with open("/proc/cpuinfo") as f:
            head = f.read().split("\n\n", 1)[0]
        for field in ("vendor_id", "cpu family", "model", "stepping",
                      "model name", "flags"):
            m = re.search(rf"^{re.escape(field)}\s*:\s*(.*)$", head,
                          re.M)
            if m:
                v = m.group(1)
                if field == "flags":
                    v = " ".join(sorted(v.split()))
                parts.append(f"{field}={v}")
    except OSError:
        pass
    if not parts:
        # /proc/cpuinfo absent (macOS, some containers): machine() +
        # processor() alone collide across x86 microarchitectures —
        # exactly the cross-model AOT misload this module exists to
        # prevent — so mix in the full platform string (OS release +
        # version) to at least separate host images; still weaker than
        # the flags fingerprint, hence kept as last resort only.
        parts = [platform.machine(), platform.processor(),
                 platform.platform()]
    # artifacts compiled under an ISA cap (--xla_cpu_max_isa, the
    # portability guard for live-migrating VMs) must not share a dir
    # with full-ISA artifacts from the same host
    m = re.search(r"--xla_cpu_max_isa=(\S+)",
                  flags.env_str("XLA_FLAGS"))
    if m:
        parts.append(f"isa={m.group(1).lower()}")
    key = "|".join(parts)
    return hashlib.sha1(key.encode()).hexdigest()[:12]
