"""The plain reference against the zoo model, at a toy size on the CPU
(kernels interpreted at L=1024). In f32 the two are the same arithmetic;
in the configuration's bf16 they differ by bf16 rounding, inside the
tolerance the chip check uses."""

import json
import os

import pytest

import compare
from conftest import BENCHMARK


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(BENCHMARK, "tests", "data", "lm-toy.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seq_len", [64, 1024])
def test_f32_program_equals_reference(toy, seq_len):
    config = dict(toy, model_params=dict(toy["model_params"], dtype="float32"))
    got = compare.compare(config, seq_len, seed=3)
    assert got["program_leaves"] == 2 + 10 * toy["model_params"]["num_layers"]
    assert got["loss_rel_error"] < 1e-6
    assert max(got["grad_rel_l2_error"].values()) < 1e-5


@pytest.mark.parametrize("seq_len", [64, 1024])
def test_bf16_program_is_inside_the_tolerance(toy, seq_len):
    got = compare.compare(toy, seq_len, seed=3)
    assert got["agree"], got
    # and not by a mile: the check has to be about bf16
    assert max(got["grad_rel_l2_error"].values()) > 1e-3


def test_a_dropped_term_fails(toy, monkeypatch):
    # the reference without the MLP's output bias: one leaf's gradient
    # is then wrong everywhere
    from reference import lm_reference

    real = lm_reference._layer

    def no_bias(x, w):
        return real(x, dict(w, b2=w["b2"] * 0.0))

    monkeypatch.setattr(lm_reference, "_layer", no_bias)
    got = compare.compare(toy, 64, seed=3)
    assert not got["agree"]
