"""``chip_smoke.py`` off the chip: without a TPU it exits non-zero and
prints no result, and its phases run end to end at a tiny size on the CPU
— the one-chip phases in process, the four-chip paths on four forced host
devices in a subprocess — so the script cannot rot between chip runs."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

TINY = dict(num_devices=12, sample_count=8, examples=300, num_classes=4,
            image_shape=(4, 4, 1), use_cnn=False)


def _phase_lines(text: str) -> list:
    return [json.loads(line) for line in text.splitlines()
            if line.startswith('{"phase"')]


def test_exits_nonzero_without_a_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_phases_run_on_cpu(capsys):
    import chip_smoke
    from benchmarks.common import BenchConfig

    chip_smoke.run_one_chip(BenchConfig(**TINY))
    lines = _phase_lines(capsys.readouterr().out)
    assert [l["phase"] for l in lines] == ["a_bank", "b_trainer",
                                           "c_run_scan", "d_arena"]
    trainer, arena = lines[1], lines[3]
    assert trainer["kernel_in_round"] is False    # jnp path off TPU
    assert trainer["round1_rel_err"] <= chip_smoke.ROUND_REL_TOL
    assert arena["lanes"] == 14 and arena["retraces"] == 0
    assert arena["lane0"]["model_rel_err"] <= chip_smoke.PATH_REL_TOL


_FOUR_DEVICES = textwrap.dedent(f"""
    import chip_smoke
    from benchmarks.common import BenchConfig
    from repro.launch.mesh import make_fl_mesh
    chip_smoke.run_four_chips(BenchConfig(**{TINY!r}), make_fl_mesh(4))
""")


def test_four_chip_paths_run_on_forced_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    engine, arena = _phase_lines(out.stdout)
    assert engine["bank_rows_per_device"] == {str(d): 3 for d in range(4)}
    assert engine["all_reduce_in_round"] is True
    assert arena["lanes_per_device"] == {str(d): 7 for d in range(4)}
