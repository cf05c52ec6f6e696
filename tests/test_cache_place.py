"""utils/cache.place_compile_cache: the one place the persistent
compilation cache is set.  Variable set -> nothing is updated (jax
reads it itself); unset -> the fixed path; never a temporary name."""

import os
import re
import tempfile

import jax

from superlu_dist_tpu.utils import cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Recorder:
    def __init__(self, monkeypatch):
        self.calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: self.calls.append((k, v)))


def test_variable_set_means_no_config_update(monkeypatch):
    rec = _Recorder(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert cache.place_compile_cache() == "/somewhere/else"
    assert cache.place_compile_cache("/ignored") == "/somewhere/else"
    assert rec.calls == []


def test_unset_means_the_fixed_path(monkeypatch):
    rec = _Recorder(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    base = os.path.join(ROOT, ".jax_cache")
    # accelerator: one un-fingerprinted directory inside the checkout
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cache.place_compile_cache() == base + "-accel"
    # CPU: the host-fingerprinted one, stable from call to call
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    cpu_dir = cache.place_compile_cache()
    assert cpu_dir == cache.host_cache_dir(base) == \
        cache.place_compile_cache()
    assert re.fullmatch(re.escape(base) + r"-[0-9a-f]{12}", cpu_dir)
    dirs = [v for k, v in rec.calls if k == "jax_compilation_cache_dir"]
    assert dirs == [base + "-accel", cpu_dir, cpu_dir]
    # never a temporary name, a pid or the time
    for d in dirs:
        assert not d.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in os.path.basename(d)


def test_explicit_path_is_used_verbatim(monkeypatch):
    rec = _Recorder(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache.place_compile_cache("/aot/xla") == "/aot/xla"
    assert ("jax_compilation_cache_dir", "/aot/xla") in rec.calls


def test_one_config_site_in_the_tree():
    """The config option is updated at one site, the helper — every
    entry script and test goes through it."""
    pat = re.compile(r"config\.update\(\s*[\"']jax_compilation_cache_dir")
    sites = []
    for top in ("superlu_dist_tpu", "tools", "tests"):
        for dp, _, fs in os.walk(os.path.join(ROOT, top)):
            sites += [os.path.join(dp, f) for f in fs
                      if f.endswith(".py")]
    sites += [os.path.join(ROOT, f) for f in
              ("chip_smoke.py", "__graft_entry__.py")]
    hits = [os.path.relpath(p, ROOT) for p in sites
            if pat.search(open(p).read())]
    assert hits == ["superlu_dist_tpu/utils/cache.py"]
