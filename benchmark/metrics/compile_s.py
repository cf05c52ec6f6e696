"""Backend-compile seconds during set-up, from jax.monitoring
(`/jax/core/compile/backend_compile_duration`; a program served by the
persistent cache still counts the time to load it)."""


def read(run):
    return run.readings["setup_compile"]["compile_s"]
