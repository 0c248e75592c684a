"""``chip_smoke.py`` refuses to run without a TPU, and each of its one-chip
phases runs end to end here at a tiny size, with the Pallas kernel in
interpret mode."""
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke


def test_refuses_cpu_and_names_the_device():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "needs a TPU" in str(e.value.code)
    assert "cpu" in str(e.value.code)


def test_mset_phase():
    chip_smoke.phase_mset(n_signals=32, n_memvec=256, n_train=1024,
                          batch=256, impl="interpret")


SERVICE_SNIPPET = """
import chip_smoke
chip_smoke.phase_service(n_signals=32, n_memvec=256, n_train=1024, batch=512,
                         impl="interpret")
"""


def test_service_phase_on_four_cpu_devices():
    """The ``--chips 4`` phase, on four virtual CPU devices in a child that
    is held to the CPU."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SERVICE_SNIPPET], cwd=root,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "sharded service, model at reg" in out.stdout


def test_scoping_phase():
    chip_smoke.phase_scoping(grid={"n_signals": [8, 16], "n_memvec": [64]},
                             n_train=256, n_surveil=64, impl="interpret")


def test_scoping_phase_fails_on_a_skipped_cell():
    """A cell whose workload cannot be built is a failure, not an
    infeasible cell."""
    with pytest.raises(chip_smoke.PhaseFailed, match="without a row"):
        chip_smoke.phase_scoping(grid={"n_signals": [-1], "n_memvec": [64]},
                                 n_train=256, n_surveil=64, impl="interpret")


@pytest.mark.parametrize("fine", [False, True], ids=["coarse", "substep4"])
def test_racing_phase(fine):
    chip_smoke.phase_racing((4, 2, 720.0), 4 if fine else 1, fine)
