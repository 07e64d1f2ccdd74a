"""``chip_smoke.py`` off the chip: it refuses any platform but TPU, and each
of its phases runs and checks at the smoke size on the CPU, so the one
chip run it is written for finds no wrong path, argument or check."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.base import get_config, reduced_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_devices(smoke):
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.require_tpu(1)


def test_script_fails_without_a_chip_and_prints_no_result():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("REPRO_", "JAX_COMPILATION"))}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    for line in r.stdout.splitlines():
        assert '"ok"' not in line, line


def test_kernel_phase_at_smoke_widths(smoke):
    smoke.check_kernels(reduced_config(get_config("qwen3-0.6b")))


def test_gateway_and_logits_phases_pass(smoke, capsys):
    router, engine = smoke.build_gateway_router(smoke=True)
    smoke.run_requests(router, engine)
    smoke.check_prefill_logits(engine)
    out = capsys.readouterr().out
    assert "8 requests" in out and "finish_reason=length" in out


def test_quarantined_request_fails_the_smoke(smoke, monkeypatch):
    """A step crash quarantines a request (finish_reason "error"); the
    gateway keeps serving, and the smoke run must not pass."""
    monkeypatch.setenv("REPRO_FAULT", "step:after=3")
    router, engine = smoke.build_gateway_router(smoke=True)
    with pytest.raises(AssertionError, match="ended with 'error'"):
        smoke.run_requests(router, engine)
    assert engine.metrics().requests_errored == 1


def test_tensor_parallel_phase_on_one_device(smoke, capsys):
    smoke.check_tensor_parallel(smoke=True, n=1)
    assert "greedy tokens identical" in capsys.readouterr().out


def test_result_line_shape(smoke, monkeypatch, capsys):
    """The last line a passing run prints, with every phase stubbed."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(smoke, "require_tpu", lambda chips: [Dev()])
    monkeypatch.setattr(smoke, "check_tensor_parallel", lambda n: None)
    monkeypatch.setattr("repro.launch.compile_cache.use_compile_cache",
                        lambda: "unused")
    assert smoke.main(["--chips", "4"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
