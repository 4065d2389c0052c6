"""Multi-pod dry-run plumbing: a fast cell lowers+compiles on the production
meshes in a subprocess (512 placeholder devices must not leak into this
test process), and the roofline reader consumes its artifact."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_this_process_has_one_device():
    assert len(jax.devices()) >= 1  # and NOT 512: the flag must not leak
    assert len(jax.devices()) < 64


@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")  # placeholder devices only
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", "smollm-135m", "--shape", "decode_32k",
           "--mesh", "both", "--out", str(tmp_path), "--tag", "t"]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for mesh in ("16x16", "2x16x16"):
        f = tmp_path / "t" / f"smollm-135m__decode_32k__{mesh}.json"
        cell = json.loads(f.read_text())
        assert cell["status"] == "ok"
        assert cell["devices"] == (256 if mesh == "16x16" else 512)
        assert "collectives" in cell and "cost_analysis" in cell

    from repro.launch.roofline import load_rows

    rows = load_rows(tmp_path / "t", "16x16")
    assert len(rows) == 1
    r = rows[0]
    assert r["dominant"] in ("compute", "memory", "collective")
    assert r["bound_step_s"] > 0


def test_mesh_factory_is_lazy():
    # Importing mesh.py must not create meshes or touch devices.
    import importlib

    import repro.launch.mesh as m

    importlib.reload(m)
    assert callable(m.make_production_mesh)


def test_input_specs_shapes():
    # input_specs uses ShapeDtypeStructs only — no allocation.
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config, shapes_for
    from repro.models.model_api import build

    for arch in ("qwen3-14b", "falcon-mamba-7b", "whisper-large-v3"):
        cfg = get_config(arch)
        b = build(cfg)
        for sname, shape in shapes_for(cfg).items():
            st = b.batch_struct(shape)
            assert all(hasattr(v, "shape") for v in st.values()), (arch, sname)
            if shape.kind == "train":
                assert st["tokens"].shape == (shape.global_batch, shape.seq_len)
