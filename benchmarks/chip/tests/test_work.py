import math

import pytest

import work

DIMS = {"n_layers": 8, "d_model": 4096, "n_heads": 32, "n_kv_heads": 2,
        "head_dim": 128, "d_ff": 13696, "vocab": 151552}


def test_peaks_of_v5e():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_linear_work_counts_stated_dtype_bytes():
    flops, nbytes = work.linear_work(8, 4096, 13696)
    assert flops == 2 * 8 * 4096 * 13696
    assert nbytes == 2 * (8 * 4096 + 4096 * 13696 + 8 * 13696)


def test_least_time_takes_the_binding_bound():
    p = work.peaks("TPU v5 lite")
    f, b = work.linear_work(8, 4096, 4096)           # decode: memory bound
    assert work.least_time_s(f, b, p) == b / p["hbm_bytes_per_s"]
    f, b = work.linear_work(4096, 4096, 4096)        # big square: compute
    assert work.least_time_s(f, b, p) == f / p["bf16_flops_per_s"]


def test_param_count_of_glm4_layers():
    # q 4096x4096, k/v 4096x256, o 4096x4096, gated MLP 3 x 4096x13696
    layer = 2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696
    assert work.layer_params(DIMS) == layer
    assert work.count_params(DIMS) == 8 * layer + 2 * 151552 * 4096


def test_served_flops_count_real_tokens_only():
    tok0 = work.token_flops(DIMS, 0)
    assert tok0 == 8 * 2 * work.layer_params(DIMS)
    # one prompt of 1 token and no decode = one token at context 1 + head
    one = work.served_flops(DIMS, [1], [])
    assert math.isclose(one, work.token_flops(DIMS, 1) + work.head_flops(DIMS))
    # decode tokens add their context's attention and a head row each
    dec = work.served_flops(DIMS, [], [10, 11])
    assert math.isclose(dec, work.token_flops(DIMS, 10)
                        + work.token_flops(DIMS, 11)
                        + 2 * work.head_flops(DIMS))
