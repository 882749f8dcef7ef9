"""The yardstick's arithmetic against hand-worked numbers: what is the same
for every model (perfbench/arith.py) and what the Llama family's shape
decides (perfbench/families/llama.py)."""
import json
import os

import pytest

from perfbench import arith
from perfbench.families import llama

CONFIGS = os.path.join(os.path.dirname(arith.__file__), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


INTERN, MISTRAL = cfg("internlm2-1.8b"), cfg("mistral-7b.l4")


@pytest.mark.parametrize("c,layer,total", [
    # q 2048x2048, k+v 2x(2048x1024), o 2048x2048, ffn 3x(2048x8192), 2 norms
    (INTERN, 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192 + 4096,
     1_889_110_016),
    # q, o 4096x4096; k, v 4096x1024; ffn 3x(4096x14336)
    (MISTRAL, 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 8192,
     1_134_596_096),
])
def test_parameter_counts(c, layer, total):
    assert llama.layer_params(c) == layer
    assert llama.total_params(c) == total
    assert llama.matmul_params(c) == total - c["vocab_size"] * c["hidden_size"]


def test_issue_25_round_numbers():
    assert round(llama.total_params(INTERN) / 1e9, 2) == 1.89
    assert round(llama.total_params(MISTRAL) / 1e9, 3) == 1.135
    assert round(24 * llama.layer_params(INTERN) / 1e9, 2) == 1.51
    assert round(llama.layer_params(MISTRAL) / 1e6) == 218


def test_kv_bytes_per_token():
    assert llama.kv_bytes_per_token(INTERN) == 96 * 1024
    one_layer = dict(MISTRAL, num_hidden_layers=1)
    assert llama.kv_bytes_per_token(one_layer) == 4 * 1024
    sixteen = dict(MISTRAL, num_hidden_layers=16)
    assert llama.kv_bytes_per_token(sixteen) == 64 * 1024


def test_weight_bytes_is_what_a_decode_step_reads():
    n = llama.total_params(INTERN) - 92544 * 2048
    assert llama.weight_bytes(INTERN) == 2 * n
    assert 3.3e9 < llama.weight_bytes(INTERN) < 3.5e9


def test_train_flops_per_token_is_bench_py_arithmetic():
    n, ne = llama.total_params(MISTRAL), 32000 * 4096
    want = 6.0 * (n - ne) + 6.0 * 4 * 32 * 128 * 2048
    assert llama.train_flops_per_token(MISTRAL, 2048) == want
    assert round(want / 1e9, 1) == 6.2      # ISSUE 25: 6.2 GFLOP per token


def test_prefill_and_decode_flops():
    d = llama.dims(INTERN)
    lin = 24 * (llama.layer_params(INTERN) - 2 * 2048)
    assert llama.prefill_flops(INTERN, 1000) == pytest.approx(
        2.0 * lin * 1000 + 2.0 * 2048 * 92544
        + 2.0 * 24 * 16 * 128 * 1000 * 1000)
    assert llama.decode_flops(INTERN, 500) == pytest.approx(
        2.0 * llama.matmul_params(INTERN) + 4.0 * 24 * 16 * 128 * 500)
    assert d["hd"] == 128 and d["KV"] == 8


@pytest.mark.parametrize("ctx0,n,rows", [(0, 1, 1), (9, 1, 10),
                                         (9, 3, 10 + 11 + 12), (99, 8, 828)])
def test_live_kv_rows(ctx0, n, rows):
    assert arith.live_kv_rows(ctx0, n) == rows


def test_live_kv_rows_is_the_sum_over_tokens():
    # token t (0-based position) reads t + 1 rows, whatever the page size
    for t0, m in [(10, 2), (16, 1), (1023, 64)]:
        assert arith.live_kv_rows(t0, m) == sum(
            t + 1 for t in range(t0, t0 + m))
    assert arith.live_kv_rows(10, 2) * llama.kv_bytes_per_token(INTERN) \
        == (11 + 12) * 96 * 1024


def test_flash_costs():
    f, b = arith.flash_fwd_cost(2, 32, 8, 2048, 128)
    assert f == 4.0 * 2 * 32 * 2048 * 2048 * 128 / 2
    assert b == 2 * 2048 * 128 * 2 * (64 + 16) + 2 * 32 * 2048 * 4
    f2, _ = arith.flash_bwd_cost(2, 32, 8, 2048, 128)
    assert f2 == 2.5 * f


def test_peaks_table():
    p = arith.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.load_peaks("TPU v9 imaginary")


@pytest.mark.parametrize("flops,byts,secs,bound", [
    (197e12, 1.0, 1.0, "compute"), (1.0, 819e9, 1.0, "memory"),
    (197e12, 2 * 819e9, 2.0, "memory")])
def test_roofline_seconds(flops, byts, secs, bound):
    got = arith.roofline_seconds(flops, byts, arith.load_peaks("TPU v5 lite"))
    assert got == (pytest.approx(secs), bound)
