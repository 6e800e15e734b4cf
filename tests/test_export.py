"""Standard export artifact: manifest, orbax params, serialized serving
function, fresh-process round trip (docs/export.md).

Parity: the reference exports a tf SavedModel any serving stack loads
(reference worker/worker.py:695-715, model_handler.py:108-141); here the
artifact is orbax + jax.export and the round trip is proven from a
subprocess that never imports the model zoo."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.export import (
    EXPORT_FORMAT,
    export_model,
    is_export_dir,
    load_export,
    make_serving_fn,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_model():
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(8)(x)
            x = nn.relu(x)
            return nn.Dense(3)(x)

    return M()


def _export_small(tmp_path):
    model = _small_model()
    x = np.random.RandomState(0).randn(4, 5).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    params = variables["params"]
    manifest = export_model(
        str(tmp_path / "exp"),
        params,
        version=42,
        metadata={"model_def": "tiny"},
        serving_fn=make_serving_fn(model, {}),
        example_features=x,
    )
    return model, params, x, manifest


def test_export_round_trip_same_process(tmp_path):
    model, params, x, manifest = _export_small(tmp_path)
    d = str(tmp_path / "exp")
    assert is_export_dir(d)
    assert manifest["format"] == EXPORT_FORMAT
    assert manifest["artifacts"]["serving_fn"], "serving plane missing"
    assert manifest["model_version"] == 42

    loaded = load_export(d)
    assert loaded.version == 42
    assert loaded.metadata["model_def"] == "tiny"
    np.testing.assert_array_equal(
        loaded.params["Dense_0"]["kernel"],
        np.asarray(params["Dense_0"]["kernel"]),
    )
    # serve through the serialized StableHLO — and at a DIFFERENT batch
    # size than the example batch (the export is batch-polymorphic)
    x2 = np.random.RandomState(1).randn(9, 5).astype(np.float32)
    got = np.asarray(loaded.serve(x2))
    want = np.asarray(
        model.apply({"params": params}, x2, training=False)
    )
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_export_legacy_chkpt_member_loads(tmp_path):
    """The artifact dir doubles as a --checkpoint_filename_for_init
    value: load_from_checkpoint_file resolves the directory."""
    from elasticdl_tpu.common.model_utils import (
        load_from_checkpoint_file,
    )

    _, params, _, _ = _export_small(tmp_path)
    version, named = load_from_checkpoint_file(str(tmp_path / "exp"))
    assert version == 42
    np.testing.assert_array_equal(
        named["Dense_0/kernel"], np.asarray(params["Dense_0"]["kernel"])
    )


def test_export_params_only_when_serving_fn_absent(tmp_path):
    params = {"w": jnp.ones((2, 2))}
    manifest = export_model(str(tmp_path / "p"), params, version=1)
    assert manifest["artifacts"]["serving_fn"] is None
    loaded = load_export(str(tmp_path / "p"))
    assert not loaded.has_serving_fn()
    with pytest.raises(RuntimeError, match="no serving function"):
        loaded.serve(np.zeros((1, 2), np.float32))


def test_newer_format_version_rejected(tmp_path):
    export_model(str(tmp_path / "v"), {"w": jnp.ones(2)}, version=1)
    mpath = tmp_path / "v" / "MANIFEST.json"
    m = json.loads(mpath.read_text())
    m["format_version"] = 99
    mpath.write_text(json.dumps(m))
    with pytest.raises(ValueError, match="newer than this loader"):
        load_export(str(tmp_path / "v"))


def test_fresh_process_source_free_serving(tmp_path):
    """The acceptance round trip: a NEW python process loads the
    artifact with generic loaders only (orbax + jax.export — not the
    framework, not the model source) and serves a batch that matches
    this process's direct forward."""
    model, params, x, _ = _export_small(tmp_path)
    x2 = np.random.RandomState(7).randn(6, 5).astype(np.float32)
    want = np.asarray(
        model.apply({"params": params}, x2, training=False)
    )
    np.save(tmp_path / "x2.npy", x2)

    code = """
import os, sys, json
import numpy as np
import jax
# the comparison below is against a CPU forward: pin the platform
# here, not through whatever JAX_PLATFORMS the caller exported — on a
# TPU host the child would otherwise serve on the chip in bf16
jax.config.update("jax_platforms", "cpu")
import orbax.checkpoint as ocp
from jax import export as jexport

d = sys.argv[1]
with open(os.path.join(d, "MANIFEST.json")) as f:
    manifest = json.load(f)
params = ocp.StandardCheckpointer().restore(
    os.path.join(d, manifest["artifacts"]["params"]))
with open(os.path.join(d, manifest["artifacts"]["serving_fn"]), "rb") as f:
    fn = jexport.deserialize(f.read())
x2 = np.load(sys.argv[2])
out = np.asarray(fn.call(params, x2))
np.save(sys.argv[3], out)
print("SERVED", out.shape)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            code,
            str(tmp_path / "exp"),
            str(tmp_path / "x2.npy"),
            str(tmp_path / "out.npy"),
        ],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SERVED (6, 3)" in proc.stdout
    got = np.load(tmp_path / "out.npy")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
