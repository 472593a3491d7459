"""The benchmark's copy of the FLOP arithmetic, its peaks table and the
harness's refusal without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
from chipbench import flops as F  # noqa: E402
from chipbench import kernels as K  # noqa: E402

# ViTDet-B at its published widths (detectron2 ViTDet
# mask_rcnn_vitdet_b_100ep.py): 12 blocks, d 768, 12 heads of 64, d_ff
# 3072, globals at blocks 2/5/8/11; everything else as ViTDet-L
VITDET_B = {"name": "vitdet-b", "n_layers": 12, "d_model": 768,
            "n_heads": 12, "n_kv_heads": 12, "head_dim": 64, "d_ff": 3072}


def _configs():
    lg = json.loads((BENCH / "configs" / "vitdet-l.json").read_text())
    return {"vitdet-l": lg["model"], "vitdet-b": dict(lg["model"], **VITDET_B)}


@pytest.mark.parametrize("name", ["vitdet-l", "vitdet-b"])
@pytest.mark.parametrize("n_low,n_reuse,beta", [
    (0, 0, 0), (0, 0, 2), (4, 0, 2), (12, 0, 2), (16, 0, 2), (4, 12, 2),
    (0, 12, 1), (8, 4, 3), (12, 4, 4)])
def test_flops_match_program(name, n_low, n_reuse, beta):
    from repro.core import vit_backbone as vb
    sys.path.insert(0, str(BENCH))
    from chipbench.harness import program_config
    model = _configs()[name]
    cfg = program_config(model)
    assert F.frame_flops(model, n_low, n_reuse, beta) == \
        vb.backbone_flops(cfg, n_low, beta, n_reuse)
    nw = F.plan_windows(model, n_low, n_reuse)
    assert F.backbone_flops_windows(model, nw, beta) == \
        vb.backbone_flops_windows(cfg, nw, beta)


def test_full_res_flops_of_l_and_b():
    c = _configs()
    assert F.frame_flops(c["vitdet-l"], 0, 0, 0) == pytest.approx(
        2.77e12, rel=0.01)
    assert F.frame_flops(c["vitdet-b"], 0, 0, 0) == pytest.approx(
        0.91e12, rel=0.05)


FLASH = ("%_flash_attention.3 = f32[4,16,4096,64]{3,2,1,0:T(8,128)} "
         "custom-call(f32[4,16,4096,64]{3,2,1,0:T(8,128)} %bitcast.127)")
WINDOW = ("%_window_attention.12 = f32[256,16,64,64]{3,2,1,0:T(8,128)} "
          "custom-call(f32[256,16,64,64]{3,2,1,0:T(8,128)} %copy.23)")


def test_attention_call_from_op_shape():
    assert K.call_shape(FLASH) == (4, (4, 16, 4096, 64))
    assert K.call_shape(WINDOW) == (4, (256, 16, 64, 64))
    assert K.call_shape("%fusion.1 = (f32[4], f32[4]) fusion()") is None
    w = F.attention_call(256, 16, 64, 64)
    assert w["flops"] == 4.0 * 256 * 16 * 64 * 64 * 64
    assert w["bytes"] == 4.0 * 4 * 256 * 16 * 64 * 64
    f = F.attention_call(4, 16, 4096, 64, itemsize=2)
    assert f["flops"] == 4.0 * 4 * 16 * 4096 * 4096 * 64
    assert f["bytes"] == 4.0 * 2 * 4 * 16 * 4096 * 64


def test_roofline_covers_the_same_calls():
    """Work and time come from the same calls: those whole in the span."""
    pk = F.peaks("TPU v5 lite")
    least = F.roofline_seconds(F.attention_call(4, 16, 4096, 64), pk)
    ns = least * 1e9
    ev = [(FLASH, 0.0, 4 * ns, {}),             # 25 % of its roofline
          (FLASH, 10 * ns, 12 * ns, {}),        # 50 %, ends past the span
          (FLASH, -ns, ns, {}),                 # starts before it
          (WINDOW, 5 * ns, 5.5 * ns, {})]
    ctx = {"device_kind": "TPU v5 lite", "device_events": ev,
           "trace_lo_ns": 0.0, "trace_hi_ns": 11 * ns}
    assert K.roofline(ctx, "_flash_attention") == pytest.approx(25.0)
    ctx["trace_hi_ns"] = 12 * ns
    assert K.roofline(ctx, "_flash_attention") == pytest.approx(
        100.0 * 2 / 6)
    assert K.roofline(dict(ctx, device_events=ev[:1]),
                      "_window_attention") is None


def test_peaks_table():
    assert F.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        F.peaks("TPU v9 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "vitdet-l.mixed-reuse", "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_without_tpu():
    p = _run(ROOT)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "cpu" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
