"""chip_smoke.py's phases at tiny sizes on the CPU, so the script that
proves the chip path cannot rot between chip runs.  The script's own
platform check lives in its ``main`` and is not called here."""
import importlib.util
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cpu_mesh():
    return Mesh(np.array(jax.devices("cpu")[:1]), ("pe",))


def test_stream_phase(smoke, cpu_mesh, capsys):
    smoke.phase_stream(1 << 12, 1 << 14, 8, mesh=cpu_mesh, ref_mesh=cpu_mesh)
    assert "bit-identical to the CPU backend" in capsys.readouterr().out


def test_stream_summary_sees_a_changed_edge(smoke):
    """The per-chunk digest the four-chip phase compares moves when one
    valid edge changes, and ignores masked slots."""
    buf = np.arange(16, dtype=np.int64).reshape(8, 2)
    mask = np.arange(8) < 6
    base = np.asarray(smoke.chunk_summary(buf, mask))
    assert base[0] == 6 and base[1] == 0
    moved = buf.copy()
    moved[2, 1] += 1
    assert np.asarray(smoke.chunk_summary(moved, mask))[2] != base[2]
    masked = buf.copy()
    masked[7] = (3, 3)
    np.testing.assert_array_equal(smoke.chunk_summary(masked, mask), base)


def test_validate_phase(smoke, capsys):
    smoke.phase_validate(1 << 12, 4)
    assert capsys.readouterr().out.count("PASS") == 2


def test_rdg_phase(smoke, capsys):
    smoke.phase_rdg(1 << 12)
    assert "exactly 3n" in capsys.readouterr().out


def test_serve_phase(smoke, capsys):
    smoke.phase_serve(8, 2)
    assert "bit-identical to generate" in capsys.readouterr().out


def test_main_refuses_the_cpu(smoke, capsys):
    """On a non-TPU platform the script names it and exits non-zero,
    printing no result line."""
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert "'cpu'" in out.err and out.out == ""


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 phase on four virtual CPU devices, in a child
    process (the device count is fixed when JAX starts)."""
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        assert len(jax.devices()) == 4
        smoke.phase_four_chips(1 << 12, 1 << 16, 16, jax.devices())
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "per-PE regrouped streams identical" in out.stdout
    assert "every request bit-identical to generate" in out.stdout
