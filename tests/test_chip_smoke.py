"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size.

The script's chip run needs a TPU. Here the same phase functions run on the
CPU (n_envs=4, t_max=2, 2 iterations, kernels interpreted), so a wrong
path, argument or check shows up before any chip time is spent; the mesh
phase runs in a child process on four forced host devices. The script must
also refuse the CPU: non-zero exit, the platform named, no result line.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(n_envs=4, t_max=2, iters=2)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


def test_main_refuses_a_cpu_and_prints_no_result(capsys):
    saved = jax.config.jax_compilation_cache_dir
    try:
        rc = cs.main([])
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
    out, err = capsys.readouterr()
    assert rc != 0
    assert "found platform 'cpu'" in err
    assert '"ok"' not in out


def test_sync_then_lockstep_pipeline_match_bitwise_on_cpu():
    device = jax.devices()[0]
    metrics = cs.phase_sync(device, **TINY)
    # rtol 0: on the CPU the lockstep pipeline reproduces ParallelRL exactly
    cs.phase_pipelined(device, metrics, **TINY, free_iters=2, rtol=0.0)


def test_process_phase_workers_report_cpu():
    cs.phase_process(jax.devices()[0], n_envs=4, t_max=2, iters=2)


def test_kernel_phase_interpreted_matches_ref():
    cs.phase_kernels(tiny=True)


def test_mesh_phase_on_four_forced_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import jax, chip_smoke; chip_smoke.phase_mesh(jax.devices(), "
            "envs_per_lane=2, t_max=2, iters=2)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "mesh plane: 4 lanes on distinct devices" in proc.stdout
    assert "matches the one-device step" in proc.stdout
