"""The serving entry point reports failed requests in its exit status."""
import sys

import pytest

from repro.launch import serve
from repro.resilience.failpoints import failpoints


@pytest.mark.parametrize("armed", [None, "sched.decode"])
def test_serve_engine_exit_status(monkeypatch, capsys, armed):
    # the compile cache is the entry point's business, not this test's
    monkeypatch.setattr("repro.compile_cache.enable_compile_cache",
                        lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen3-8b", "--smoke", "--engine", "--batch", "2",
        "--prompt-len", "6", "--new-tokens", "3", "--slots", "2"])
    if armed is None:
        serve.main()
        assert "engine drained 2 requests" in capsys.readouterr().out
        return
    with failpoints({armed: "always"}), pytest.raises(SystemExit) as exc:
        serve.main()
    # a non-zero status that names the error, never "drained"
    assert exc.value.code not in (0, None)
    assert "did not finish" in str(exc.value.code)
    assert "injected failpoint 'sched.decode'" in str(exc.value.code)
    captured = capsys.readouterr()
    assert "drained" not in captured.out
    assert "failed" in captured.err


@pytest.mark.parametrize("env_dir", [None, "placed-from-outside"])
def test_compile_cache_location(monkeypatch, env_dir):
    import os

    import jax

    from repro.compile_cache import checkout_root, enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = enable_compile_cache()
        if env_dir is None:
            # one fixed, gitignored directory inside the checkout
            assert got == os.path.join(checkout_root(), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(checkout_root(), ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
        else:
            # the variable is JAX's own: nothing is set in code
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
