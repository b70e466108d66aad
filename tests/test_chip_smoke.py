"""``chip_smoke.py`` rehearsed on CPU: every phase's code at tiny sizes
(Pallas in interpret mode, so the Mosaic-kernel check is off), the
four-chip phase on four forced host devices, and the refusal to run
anywhere but on a TPU."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke_mod():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(smoke_mod):
    with smoke_mod.Smoke(expect_kernel=False) as s:
        yield s
    assert not s.failures, s.failures


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src") + os.pathsep
                + os.environ.get("PYTHONPATH", ""))


def test_refuses_cpu_without_running_a_phase():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "needs a TPU" in out.stderr
    assert out.stdout == ""


def test_paper_phase(smoke_mod, smoke):
    smoke_mod.phase_paper(smoke)


def test_flat100m_phase_tiny(smoke_mod, smoke):
    smoke_mod.phase_flat100m(smoke, hidden=64, k=2)


def test_fed100m_phase_tiny(smoke_mod, smoke):
    from repro.configs import get_config
    cfg = get_config("fed100m").reduced(n_layers=2, d_model=128)
    smoke_mod.phase_fed100m(smoke, cfg=cfg, k=2, seqs=2, seq_len=64,
                            rounds=2)


_FOUR_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import jax
    assert jax.device_count() == 4, jax.device_count()
    import chip_smoke
    with chip_smoke.Smoke(expect_kernel=False) as s:
        chip_smoke.phase_four_chips(s, hidden=64, k=2)
    assert not s.failures, s.failures
    print("FOUR_OK")
""")


def test_four_chips_phase_on_host_devices():
    env = _env()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_SCRIPT.format(root=str(ROOT))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "FOUR_OK" in out.stdout
