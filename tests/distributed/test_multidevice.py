"""Run multi-device semantics tests in subprocesses (8 fake CPU devices).

The main pytest process keeps a single device (per task spec); each case gets
a fresh interpreter with XLA_FLAGS set before jax import.
"""

import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # each case is a fresh 8-fake-device subprocess

ROOT = Path(__file__).resolve().parents[2]

CASES = [
    "systolic_equals_psum",
    "systolic_tree",
    "train_systolic_equals_auto",
    "moe_ep_multidevice_matches_dense",
    "elastic_checkpoint_reshard",
    "compressed_train_step_runs",
    "sp_model_same_loss",
    # mesh-of-HMCs data parallelism: run_pallas on a sharded train-step
    # program vs jax.grad at 1, 4, and 16 simulated devices (each case
    # pins its own --xla_force_host_platform_device_count in run_cases)
    "mesh_dp_grads_1",
    "mesh_dp_grads_4",
    "mesh_dp_grads_16",
    # 2D (pipeline x tensor) sharding through the same jax.grad oracle
    "mesh_2d_grads_4",
]


@pytest.mark.parametrize("case", CASES)
def test_case(case):
    env = {"PYTHONPATH": f"{ROOT / 'src'}:{ROOT}"}
    import os

    env.update({k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)})
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT}"
    proc = subprocess.run(
        [sys.executable, "-m", "tests.distributed.run_cases", case],
        capture_output=True,
        text=True,
        cwd=ROOT,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, f"{case} failed:\n{proc.stdout}\n{proc.stderr}"
    assert f"PASS {case}" in proc.stdout
