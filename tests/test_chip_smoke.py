"""The bring-up plumbing on the CPU backend: ``chip_smoke.py`` at tiny
size, the device-kind -> hardware-spec table, and the compile-cache
placement."""
import dataclasses
import os
import sys

import jax
import pytest

from repro.core.hw import (DEVICE_KIND_SPECS, TPU_V5E, describe_plan_hw,
                           spec_for_device)
from repro.launch import compile_cache

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402


@dataclasses.dataclass
class _Device:
    platform: str
    device_kind: str


def test_chip_smoke_runs_at_tiny_on_cpu():
    lines = []
    out = chip_smoke.run_smoke("tiny", "cpu", logit_tol=1e-3,
                               log=lines.append)
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert out["max_logit_diff"] <= 1e-3
    assert set(out["compile_s"]) == {"resident", "offload-fp", "offload-q8"}
    text = "\n".join(lines)
    # the plan names the rig and the device it was made for
    assert "plan hw=tpu-v5e-host for device platform=cpu" in text
    assert "(moved=True)" in text and "(moved=False)" not in text


def test_chip_smoke_refuses_an_unexpected_platform():
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'cpu'"):
        chip_smoke.run_smoke("tiny", "tpu", log=lambda s: None)


def test_chip_smoke_main_prints_no_result_off_the_chip(monkeypatch, capsys):
    # keep the persistent cache off in this process
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "platform 'cpu'" in out.err


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_kinds_plan_with_the_v5e_spec(kind):
    assert spec_for_device(_Device("tpu", kind)) is TPU_V5E


def test_unknown_tpu_kind_is_an_error():
    with pytest.raises(ValueError, match="TPU v99"):
        spec_for_device(_Device("tpu", "TPU v99"))


def test_cpu_backend_keeps_the_v5e_default():
    assert DEVICE_KIND_SPECS["cpu"] is TPU_V5E
    assert spec_for_device() is TPU_V5E          # the real CPU device
    assert "platform=cpu kind=cpu" in describe_plan_hw(TPU_V5E)


def test_compile_cache_dir_honours_the_environment(tmp_path):
    assert compile_cache.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}) == str(tmp_path)
    fixed = compile_cache.compile_cache_dir({})
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fixed == os.path.join(root, ".jax_compile_cache")


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_enable_compile_cache_sets_a_dir_only_when_unset(monkeypatch,
                                                         tmp_path, env_dir):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert compile_cache.enable_compile_cache() == want
    if env_dir is None:
        assert calls["jax_compilation_cache_dir"] == want
    else:
        assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
