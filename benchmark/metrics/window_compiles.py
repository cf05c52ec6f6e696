"""Programs that reached the backend compiler inside the measured
window; 0 is the expected reading."""


def read(run):
    return run.readings["window_compile"]["backend_compiles"]
