"""Test configuration: force an 8-virtual-device CPU platform so mesh
sharding tests run anywhere and never grab the real TPU chip (the
reference's analog is the oversubscribed-local-MPI-ranks CTest sweep,
TEST/CMakeLists.txt:48-53).  The suite is the CPU correctness tier;
what runs on the chip is chip_smoke.py."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Cap codegen at AVX2 so cached CPU executables are PORTABLE across
# host models: this pool live-migrates VMs between CPU generations
# mid-session, and model-tuned AOT artifacts (+prefer-no-scatter etc.)
# executed on the other model produced NaN solves and a SIGSEGV
# (cpu_aot_loader cross-model warnings).  Correctness tests don't
# need AVX512 throughput.
import sys  # noqa: E402
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from superlu_dist_tpu.utils.cache import (ensure_portable_cpu_isa,  # noqa: E402
                                          host_cache_dir,
                                          place_compile_cache)

os.environ["XLA_FLAGS"] = ensure_portable_cpu_isa(flags)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# persistent compile cache: the suite re-jits the same group programs
# every run; caching cuts a cold 20-minute run to a few minutes.
# The directory is fingerprinted by host CPUID/flags — XLA:CPU AOT
# entries from a different machine type misload (cpu_aot_loader
# SIGILL/wrong-code warning; observed as flaky numerics).  Passed
# explicitly so the helper need not initialize the backend here; a
# JAX_COMPILATION_CACHE_DIR from outside still wins.
place_compile_cache(host_cache_dir(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")))
# With a compile cache placed, the exported-program store
# (resilience/aot.py) is on by its own rule, in `<that cache>/slu_aot`:
# whole-phase factor / packed-solve builds DESERIALIZE their exported
# programs instead of re-tracing — the suite builds hundreds of them.
# Entries are keyed by the package's sources, so an edit re-keys them.


# --- hang containment -----------------------------------------------
# The resilience work (tests/test_resilience.py, serve chaos paths)
# exists precisely because a future that never resolves would
# otherwise HANG a test, eat the tier-1 870 s budget and fail the
# whole suite with no traceback.  Two layers make a hang loud instead:
# faulthandler (SIGSEGV/deadlock tracebacks always on) and a per-test
# SIGALRM guard that raises TimeoutError in the test after
# SLU_TEST_TIMEOUT seconds (default 300), with a faulthandler
# hard-exit backstop 60 s later for hangs the signal cannot interrupt.
import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

faulthandler.enable()

import pytest  # noqa: E402

_TEST_TIMEOUT_S = float(os.environ.get("SLU_TEST_TIMEOUT", "300") or 0)


@pytest.fixture(autouse=True)
def _per_test_hang_guard(request):
    # deliberately-long opt-in suites (the ~30-min scale
    # certification, slow serve loads) are exempt: their length is
    # the point, not a hang
    if any(request.node.get_closest_marker(m)
           for m in ("scale", "slow")):
        yield
        return
    if (_TEST_TIMEOUT_S <= 0 or os.name != "posix"
            or threading.current_thread()
            is not threading.main_thread()):
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded SLU_TEST_TIMEOUT={_TEST_TIMEOUT_S:.0f}s "
            "(likely a hung future/lock — see the resilience "
            "containment contracts)")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    # backstop: a hang inside C code never delivers the Python-level
    # signal handler; dump all stacks and kill the process instead of
    # silently eating the suite budget
    faulthandler.dump_traceback_later(_TEST_TIMEOUT_S + 60, exit=True)
    signal.setitimer(signal.ITIMER_REAL, _TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, old)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "scale: target-scale end-to-end runs (≥10⁵ dof, ~30+ min on "
        "a 1-core host) — excluded from the default suite; run with "
        "`pytest -m scale`")
    config.addinivalue_line(
        "markers",
        "slow: heavy serve/load tests (minutes of wall clock) — "
        "excluded from tier-1 (`-m 'not slow'`) and from the default "
        "suite; run with `pytest -m slow`")


def pytest_collection_modifyitems(config, items):
    import pytest
    expr = config.getoption("-m") or ""
    for name in ("scale", "slow"):
        if name in expr:
            # the caller's -m expression names this marker — pytest's
            # own selection decides (so `-m scale` opts in, and
            # `-m 'not slow'` deselects).  Markers NOT named in the
            # expression still get the default opt-out below: tier-1's
            # `-m 'not slow'` must not accidentally run the 30-minute
            # scale certification.
            continue
        skip = pytest.mark.skip(reason=f"{name} run: opt in with "
                                       f"-m {name}")
        for item in items:
            if name in item.keywords:
                item.add_marker(skip)


@pytest.fixture
def aot_store(tmp_path, monkeypatch):
    """A compile cache kept at tmp_path (through the one helper that
    places it): the exported-program store (resilience/aot.py) is then
    its `slu_aot` sub-directory, by its rule alone.  Yields that
    directory."""
    from superlu_dist_tpu.resilience import aot
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    place_compile_cache(str(tmp_path))
    yield aot.aot_dir()
    place_compile_cache(old)
