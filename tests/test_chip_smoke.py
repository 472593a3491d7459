"""chip_smoke.py off the chip: its serving flow runs end to end on the
64-wide SIM config (kernels interpreted), and the script itself refuses
to run, with no result line, where JAX finds no TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

from repro.configs.vitdet_l import SIM

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_flow_serves_sim():
    lines = []
    _load().smoke(SIM, log=lines.append)
    text = "\n".join(lines)
    assert "warmup: 8 executables" in text
    assert "0 steady-state compiles" in text
    assert text.count("features ") == 2


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok": true' not in out.stdout
