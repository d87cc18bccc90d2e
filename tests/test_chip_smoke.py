"""chip_smoke.py refuses to run without a TPU, and its one-chip path
passes end to end at a tiny size on the CPU (Pallas in interpret mode);
entry points put the compile cache where the contract says."""
import importlib.util
import json
import os
import pathlib

import jax
import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # keep this test process's compile-cache setting untouched
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda root: "unchanged")
    return mod


def test_no_tpu_exits_without_result(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_path_on_cpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "require_tpu", lambda jax: ("cpu", "cpu", 1))
    monkeypatch.setattr(smoke, "check_mosaic", lambda jnp, srv: 0)
    monkeypatch.setattr(smoke, "FABRICS", ("fir", "gcd"))
    smoke.ONE_CHIP.update(slots=8, requests=12, max_len=8, sample=4)
    assert smoke.main(["--seed", "3"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert any("fir pallas schedule='auto'" in line for line in out)
    assert json.loads(out[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_compile_cache_location(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "e"))
        assert compile_cache.enable_compile_cache(tmp_path) \
            == str(tmp_path / "e")
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(str(tmp_path), ".jax_cache")
        assert compile_cache.enable_compile_cache(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
