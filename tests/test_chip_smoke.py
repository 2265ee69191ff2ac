"""chip_smoke.py off the chip: it refuses a CPU, its phase logic drains and
checks a tiny model end to end, and the compile cache follows
``JAX_COMPILATION_CACHE_DIR``."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke at a tiny size: a one-layer smoke config with 4 kv heads,
    short prompts, no kernel check (the CPU runs the kernel interpreted)."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import repro.launch.serve as launch
    from repro.configs import ARCHS, override, smoke_config
    from repro.models import build

    real = launch.build_bundle

    def tiny(arch, **kw):
        b = real(arch, **kw)
        cfg = override(smoke_config(ARCHS[arch]), num_heads=8,
                       num_kv_heads=4)
        return build(cfg, b.flags)

    monkeypatch.setattr(launch, "build_bundle", tiny)
    monkeypatch.setattr(mod, "MAX_LEN", 128)
    monkeypatch.setattr(mod, "PROMPT_LEN", (8, 65))
    monkeypatch.setattr(mod, "MAX_NEW", 8)
    monkeypatch.setattr(mod, "check_kernel", lambda eng: None)
    return mod


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_main_at_tiny_size(smoke, monkeypatch, tmp_path, capsys):
    """The whole default phase on the CPU: drain, reference check, warm
    drain; the device JSON is the last stdout line."""
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(smoke, "check_device", lambda count: jax.devices()[0])
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: str(tmp_path))
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert any("exact argmax" in ln for ln in lines)
    assert any("identical tokens" in ln for ln in lines)
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": jax.device_count()}}


def test_reference_check_rejects_a_wrong_token(smoke):
    from repro.launch.serve import build_bundle, build_pool

    bundle = build_bundle("phi4-mini-3.8b")
    pool = build_pool(bundle, None, batch_size=4, max_len=smoke.MAX_LEN,
                      cache_backend="paged")
    reqs, _, _ = smoke.drain(pool, bundle.cfg.vocab_size, 0)
    first = min(reqs, key=lambda r: r.rid)
    first.out_tokens[:] = [(t + 1) % bundle.cfg.vocab_size
                           for t in first.out_tokens]
    with pytest.raises(smoke.SmokeFailure, match="below the reference"):
        smoke.reference_check(bundle, pool.engines[0], reqs)


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(tmp_path, monkeypatch, env_dir):
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir:
            # JAX reads the variable itself; nothing here overrides it
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
