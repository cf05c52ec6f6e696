"""The yardstick's own tests, run by tier-1: every test of
`benchmark/tests` collected here as it stands, one class a module
(`TestCorrect::test_sound_run_is_correct[...]` is
`benchmark/tests/test_correct.py::test_sound_run_is_correct[...]`).

Those modules say `from conftest import ...` and `from test_correct
import ...`: while they are imported their directory leads `sys.path`
and `conftest` names the benchmark's, not this directory's; both are
put back after.  A benchmark test that fails here is a finding about
the benchmark or the program, not a test to skip."""

import importlib
import importlib.util
import os
import sys

HERE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")


def _load():
    spec = importlib.util.spec_from_file_location(
        "conftest", os.path.join(HERE, "conftest.py"))
    theirs = importlib.util.module_from_spec(spec)
    ours = sys.modules.get("conftest")
    sys.modules["conftest"] = theirs
    sys.path.insert(0, HERE)
    try:
        spec.loader.exec_module(theirs)
        for f in sorted(os.listdir(HERE)):
            if not (f.startswith("test_") and f.endswith(".py")):
                continue
            mod = importlib.import_module(f[:-3])
            own = {k: v for k, v in vars(mod).items()
                   if getattr(v, "__module__", None) == mod.__name__}
            # module-level fixtures serve the class from this module
            globals().update({
                k: v for k, v in own.items()
                if hasattr(v, "_fixture_function_marker")})
            cls = "Test" + f[5:-3].title().replace("_", "")
            globals()[cls] = type(cls, (), {
                k: staticmethod(v) for k, v in own.items()
                if k.startswith("test_") and callable(v)})
    finally:
        sys.path.remove(HERE)
        if ours is None:
            del sys.modules["conftest"]
        else:
            sys.modules["conftest"] = ours


_load()
