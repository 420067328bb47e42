"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases pass at tiny size on the CPU (interpret-mode kernels)."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(cmd, cwd, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(env_over)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    res = _run([sys.executable, "chip_smoke.py"], ROOT)
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert '"ok"' not in res.stdout


def test_chip_smoke_refuses_outside_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run([sys.executable, "chip_smoke.py"], tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_chip_smoke_phases_tiny():
    """Serve (kernel forced on, interpret mode), train and platform
    phases on the reduced configs."""
    spec = chip_smoke.serve_spec(reduced=True, batch=2, prompt_len=16,
                                 gen=4, requests=3, use_pallas=True)
    direct = chip_smoke.run_serve(spec)
    assert direct["use_pallas"]
    assert direct["kernel_err"] <= 1e-5
    assert len(direct["responses"]) == 3
    chip_smoke.run_train(chip_smoke.train_spec(
        reduced=True, total_steps=2, global_batch=2, seq_len=16))
    chip_smoke.run_platform(spec, direct["responses"])


def test_chip_smoke_four_chip_phase_tiny():
    """The --four-chips phase on four virtual CPU devices."""
    script = textwrap.dedent("""
        import sys
        sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
        import jax
        assert jax.device_count() == 4, jax.device_count()
        import chip_smoke
        from repro.configs import get_config
        chip_smoke.run_four_chips(get_config("paper-overhead-100m").reduced(),
                                  batch=4, seq=32)
        print("OK")
    """)
    res = _run([sys.executable, "-c", script, str(ROOT)], ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert res.returncode == 0 and "OK" in res.stdout, \
        res.stdout[-2000:] + res.stderr[-3000:]
    assert "2x2 vs one chip" in res.stdout
