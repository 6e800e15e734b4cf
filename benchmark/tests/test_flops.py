"""The FLOP and roofline arithmetic against counts made by hand."""

import json
import os

import pytest

import flops
from conftest import BENCHMARK


def _config(name):
    with open(os.path.join(BENCHMARK, "configs", name + ".json")) as f:
        return json.load(f)["model_params"]


def test_lm_125m_by_hand():
    p = _config("lm-125m")
    # a layer: q, k, v, o of 768 x 768 and an MLP of 768 x 3072 twice
    layer = 4 * 768 * 768 + 2 * 768 * 3072
    assert layer == 7_077_888
    head = 50_304 * 768
    assert flops.matmul_params(p) == 12 * layer + head == 123_568_128
    # attention: 6 * L * 12 heads * 64 * 12 layers
    assert flops.train_flops_per_token(p, 2048) == 741_408_768 + 113_246_208
    assert flops.train_flops_per_token(p, 512) == 741_408_768 + 28_311_552
    assert round(flops.train_flops_per_token(p, 2048) / 1e6) == 855
    assert round(flops.train_flops_per_token(p, 512) / 1e6) == 770


def test_lm_350m_by_hand():
    p = _config("lm-350m")
    layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    assert flops.matmul_params(p) == 24 * layer + 50_304 * 1024 == 353_501_184
    assert flops.train_flops_per_token(p, 2048) == 2_121_007_104 + 301_989_888


def test_mfu_reproduces_the_ledger():
    # the driver's PR 22 medians: 99,223 tokens/s/chip was 43.0%,
    # 121,478 at L=512 was 47.5%
    p = _config("lm-125m")
    assert flops.mfu_percent(99_223, p, 2048, "TPU v5 lite") == pytest.approx(43.0, abs=0.05)
    assert flops.mfu_percent(121_478, p, 512, "TPU v5 lite") == pytest.approx(47.5, abs=0.05)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")


@pytest.mark.parametrize(
    "kernel,matmuls", [("edl_flash_fwd", 2), ("edl_flash_bwd_dq", 3), ("edl_flash_bwd_dkv", 4)]
)
def test_flash_kernel_cost(kernel, matmuls):
    # 8 sequences x 12 heads, L 2048, head 64: each causal L x L x D
    # matmul is 2 * L^2 * D / 2 FLOPs
    ops, nbytes = flops.flash_kernel_cost(kernel, 96, 2048, 64)
    assert ops == matmuls * 96 * 2048 * 2048 * 64
    seconds, bound = flops.roofline(ops, nbytes, "TPU v5 lite")
    assert bound == "compute"
    assert seconds == pytest.approx(ops / 197e12)


def test_flash_roofline_reproduces_the_ledger():
    # PR 22's traced dp4 run: 192 forward calls in 0.27621 s were 18.186%
    ops, nbytes = flops.flash_kernel_cost("edl_flash_fwd", 96, 2048, 64)
    least, _ = flops.roofline(ops, nbytes, "TPU v5 lite")
    assert 100 * 192 * least / 0.2762076785 == pytest.approx(18.186, abs=0.01)
