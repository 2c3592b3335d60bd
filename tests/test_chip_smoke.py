"""chip_smoke.py on the CPU: its phases at tiny sizes (Pallas in interpret
mode), its refusal to report without a TPU, and the compile-cache helper."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.kernels import ops as kops
from repro.launch import compile_cache

REPO = Path(__file__).resolve().parent.parent
TINY_SOLVER = dict(
    max_enumerate=64, n_samples=32, batch_size=16, refine_rounds=1, refine_pool=16
)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def on_cpu(smoke, monkeypatch):
    """Steer the Mosaic check to this host: the kernel must run in
    interpret mode here, and its lowering has no TPU custom call."""
    assert jax.default_backend() == "cpu"

    def interpret_only(lowered_text):
        assert kops._interpret()
        assert "tpu_custom_call" not in lowered_text

    monkeypatch.setattr(smoke, "assert_mosaic", interpret_only)
    return smoke


def test_mosaic_check_refuses_interpret_mode(smoke):
    with pytest.raises(smoke.SmokeFailure, match="interpret mode"):
        smoke.assert_mosaic("tpu_custom_call")


@pytest.mark.parametrize("phase", ["stage1", "stage2"])
def test_kernel_phases_tiny(on_cpu, phase, capsys):
    getattr(on_cpu, f"phase_{phase}")(n_jobs=2, rows_per_job=8)
    assert "PASS" in capsys.readouterr().out


def test_fleet_phase_tiny(on_cpu):
    fleet = on_cpu.phase_fleet(n_jobs=2, solver_kwargs=TINY_SOLVER)
    assert len(fleet.results) == 2 and fleet.n_stage1_launches > 0


def test_serve_phase_tiny(on_cpu, capsys):
    res = on_cpu.phase_serve(
        n_jobs=6, rate=0.1, solver_kwargs=TINY_SOLVER, min_peak_queue=2
    )
    assert res.n_jobs == 6
    out = capsys.readouterr().out
    assert "every job served" in out and "jobs/s" in out


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


def run_python(args, cwd, **env_overrides):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **env_overrides)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = run_python(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0 and '"ok"' not in out.stdout


def test_sharded_phase_on_four_host_devices():
    """The --chips 4 phase on four virtual CPU devices (never the chip)."""
    call = f"phase_sharded(n_jobs=2, rows_per_job=8, solver_kwargs={TINY_SOLVER!r})"
    code = f"import chip_smoke\nchip_smoke.{call}\n"
    out = run_python(
        ["-c", code], REPO,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.count("PASS") == 2 and "stage 1 is not sharded" in out.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == str(REPO / ".jax_cache")
