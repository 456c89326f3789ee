"""chip_smoke.py off the chip: at ``--reduced`` size on the CPU every
phase runs and passes, and the script still refuses to report a result —
it prints ``"ok": true`` only at published width on a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _no_result(out: str) -> None:
    assert '"ok": true' not in out
    assert '"ok"' not in out.strip().splitlines()[-1]


def test_refuses_off_chip_before_running_anything(smoke, capsys):
    assert smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_reduced_runs_every_phase_and_reports_no_result(smoke, capsys):
    assert smoke.main(["--reduced"]) == 3
    out = capsys.readouterr().out
    for phase in ("engine", "kernel", "serve"):
        assert f"phase {phase}: ok" in out, out
    for S in smoke.WINDOWS:
        assert f"kernel S={S}:" in out
    assert "smoke timings" in out and "compiles:" in out
    assert "peak_bytes_in_use" in out
    _no_result(out)


def test_four_chip_phase_needs_four_devices(smoke):
    with pytest.raises(smoke.SmokeFailure, match="needs 4 devices"):
        smoke.main(["--reduced", "--chips", "4"])


def test_four_chip_phase_on_forced_cpu_devices():
    """The parallel-services phase on four host devices: one NER service
    per device, parallel == sequential, four distinct devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, str(SCRIPT), "--reduced",
                          "--chips", "4"], capture_output=True, text=True,
                         env=env, cwd=ROOT, timeout=300)
    assert res.returncode == 3, res.stderr[-3000:]
    assert "phase multimodel: ok" in res.stdout
    assert "4 distinct devices" in res.stdout
    assert "phase serve" not in res.stdout
    _no_result(res.stdout)
