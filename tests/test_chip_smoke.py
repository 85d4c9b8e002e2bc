"""`chip_smoke.py` off the chip: its phases and checks at the smoke config on
the CPU backend, and its refusal to report anything without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def test_phases_pass_on_smoke_config():
    """Phases a, b and c with every check, one CPU device, smoke widths."""
    from repro.configs import sinkhorn_wmd as wmd_cfg
    from repro.launch.mesh import make_mesh
    smoke = _chip_smoke()
    out = smoke.run(make_mesh((1, 1), ("data", "model")),
                    wmd_cfg.smoke_config())
    assert set(out["phases"]) == {"main", "live"}
    main = out["phases"]["main"]
    for key in ("ref_a", "ref_b"):
        assert main[key]["tol_share"] <= 1.0
    assert out["phases"]["live"]["ref_c"]["docs"] > 0
    assert main["warmup"]["compiles"] > 0


def test_check_rejects_a_distance_off_the_reference():
    import numpy as np
    smoke = _chip_smoke()
    ref = np.array([20.0, 25.0, 0.01])
    assert smoke.compare("ok", ref * (1 + 1e-3), ref)["tol_share"] < 1
    with pytest.raises(smoke.SmokeFailure):
        smoke.compare("bf16-sized", ref * (1 + 2e-2), ref)
    with pytest.raises(smoke.SmokeFailure):
        smoke.compare("self-cost", ref + np.array([0, 0, 0.3]), ref)


def test_four_device_mesh_matches_one_device():
    """The --chips 4 path (phases a and b on a (4, 1) mesh, compared with
    one device) on four virtual CPU devices."""
    code = (
        "import sys, jax; sys.path.insert(0, sys.argv[1]); import chip_smoke\n"
        "from repro.configs import sinkhorn_wmd as c\n"
        "from repro.launch.mesh import make_mesh\n"
        "d = jax.devices()\n"
        "out = chip_smoke.run(make_mesh((4, 1), ('data', 'model'), "
        "devices=d[:4]), c.smoke_config(), live=False, "
        "cmp_mesh=make_mesh((1, 1), ('data', 'model'), devices=d[:1]))\n"
        "assert out['phases']['main']['devices'] == 4\n"
        "print('MESH_OK', out['mesh_agreement']['top_k']['tol_share'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-c", code, REPO], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "MESH_OK" in res.stdout


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_no_result_without_tpu_or_checkout(where, tmp_path):
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert ("no TPU found" if where == "checkout"
            else "src/repro not found") in res.stderr
