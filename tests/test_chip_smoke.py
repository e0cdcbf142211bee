"""``chip_smoke.py`` never runs on the CPU, and the compile-cache helper
that every entry point calls puts the cache where it says."""
import os
import sys

import jax

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_refuses_cpu_before_building_a_model(monkeypatch,
                                                        tmp_path, capsys):
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    from repro.core import eo_adapter as EO

    def no_model(*a, **kw):
        raise AssertionError("chip_smoke built a model without a TPU")

    monkeypatch.setattr(EO, "init_adapter", no_model)
    # an explicit cache directory: the helper then leaves this process's
    # jax config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache as CC
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert CC.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = CC.enable_compile_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
