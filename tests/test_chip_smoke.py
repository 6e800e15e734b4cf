"""chip_smoke.py off the chip: its CPU rehearsal passes and says
everything the chip run must say; without the rehearsal argument a
machine with no chip makes it fail; and the flash kernels refuse a CPU
backend that nobody asked for."""

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

REQUIRED_KEYS = {
    "platform",
    "device_kind",
    "device_count",
    "steps",
    "first_loss",
    "last_loss",
    "attention",
    "record_reader",
    "compile_cache_dir",
    "establish_seconds",
    "wall_seconds",
}


def _run_smoke(*argv):
    return subprocess.run(
        [sys.executable, SMOKE, *argv],
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        timeout=600,
    )


def test_rehearsal_passes_and_reports_every_required_key():
    proc = _run_smoke("--rehearse-on-cpu")
    assert proc.returncode == 0, proc.stderr[-4000:]
    result, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    # the last line is the verdict with exactly these keys; the report
    # rides the line before it
    assert verdict == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 2},
    }
    assert REQUIRED_KEYS <= set(result), REQUIRED_KEYS - set(result)
    assert result["rehearsal"] is True
    assert (result["platform"], result["device_kind"]) == ("cpu", "cpu")
    assert result["device_count"] == 2
    assert result["steps"] == 16 and result["tasks"] == 8
    assert result["checkpoint_version"] == 16
    assert result["attention"] == "pallas-interpret"
    assert result["record_reader"] in ("native", "python")
    assert result["last_loss"] < result["first_loss"]
    assert result["kernel_leg"]["interpret"] is True


@pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="this machine has a chip: there the chip check passes",
)
def test_without_the_rehearsal_argument_no_chip_means_failure():
    """The suite's environment says JAX_PLATFORMS=cpu; the chip check
    must not carry on there."""
    proc = _run_smoke()
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout[-2000:]
    assert "chip_smoke: FAILED" in proc.stderr


def test_flash_kernels_refuse_a_cpu_backend_nobody_asked_for(monkeypatch):
    """CPU by request (the suite's own platform pin) interprets; the
    same CPU backend with the platform left open, which is where a
    failed TPU start-up lands, raises."""
    import jax

    from elasticdl_tpu.ops import flash_attention

    assert jax.default_backend() == "cpu"
    assert flash_attention.kernel_interpret_mode() is True
    pinned = jax.config.jax_platforms
    try:
        # the live CPU backend stays; only what was asked for changes
        jax.config.update("jax_platforms", None)
        with pytest.raises(RuntimeError, match="failed to initialise"):
            flash_attention.kernel_interpret_mode()
        jax.config.update("jax_platforms", "tpu,cpu")
        with pytest.raises(RuntimeError, match="failed to initialise"):
            flash_attention.kernel_interpret_mode()
    finally:
        jax.config.update("jax_platforms", pinned)
