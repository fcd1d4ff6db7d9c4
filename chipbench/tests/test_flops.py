"""FLOP and byte arithmetic, against the published parameter count."""
import json
import pathlib

import pytest

from benchlib import flops, kernels
from benchlib.peaks import peaks_for

CONFIG = json.loads((pathlib.Path(__file__).resolve().parent.parent
                     / "configs" / "chatglm3-6b.json").read_text())


def dims():
    a = CONFIG["arch"]
    return flops.DenseDims(layers=a["layers"], d_model=a["d_model"],
                           heads=a["heads"], kv_heads=a["kv_heads"],
                           head_dim=a["head_dim"], d_ff=a["d_ff"],
                           vocab=a["vocab"])


def test_matmul_params_match_the_published_count():
    d = dims()
    # + embedding, QKV biases and norm scales: 6.244B in all
    total = (d.layers * d.layer_matmul_params + 2 * d.head_params
             + d.layers * (d.heads + 2 * d.kv_heads) * d.head_dim
             + (2 * d.layers + 1) * d.d_model)
    assert total == pytest.approx(6.244e9, rel=1e-3)


def test_prefill_and_decode_flops():
    d = dims()
    per_token = 2.0 * d.layers * d.layer_matmul_params
    attn = 4.0 * d.layers * d.heads * d.head_dim
    assert flops.prefill_flops(d, 3) == pytest.approx(
        3 * per_token + attn * 6 + 2 * d.head_params)
    # a cached prefix of 2: one new token attending over 3 keys
    assert flops.prefill_flops(d, 3, cached=2) == pytest.approx(
        per_token + attn * 3 + 2 * d.head_params)
    assert flops.decode_flops(d, 10) == pytest.approx(
        per_token + 2 * d.head_params + attn * 10)


def test_least_time_is_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    f, b = flops.gemm_cost(8, 4096, 4096)
    assert b == (8 * 4096 + 4096 * 4096 + 8 * 4096) * 2
    assert flops.least_time(f, b, p) == pytest.approx(b / 819e9)
    f, b = flops.gemm_cost(4096, 4096, 4096)
    assert flops.least_time(f, b, p) == pytest.approx(f / 197e12)
    f, _ = flops.flash_cost(2, 4, 4, 8, causal=True)
    assert f == 4.0 * 2 * 8 * 10


def test_kernel_least_time_from_hlo_text():
    p = peaks_for("TPU v5 lite")
    text = ("%custom-call.7 = bf16[8,13696]{1,0} custom-call(bf16[8,4096]"
            "{1,0} %x, bf16[4096,13696]{1,0} %w), custom_call_target="
            "\"tpu_custom_call\"")
    want = (8 * 4096 + 4096 * 13696 + 8 * 13696) * 2 / 819e9
    assert kernels.gemm_least(text, p) == pytest.approx(want)
    fl = ("%custom-call.2 = bf16[64,2048,128]{2,1,0} custom-call(s32[64]{0} "
          "%ks, bf16[64,2048,128]{2,1,0} %q, bf16[64,2048,128]{2,1,0} %k, "
          "bf16[64,2048,128]{2,1,0} %v)")
    f, _ = flops.flash_cost(64, 2048, 2048, 128, causal=True)
    nbytes = 64 * 4 + 4 * 64 * 2048 * 128 * 2
    assert kernels.flash_least(fl, p) == pytest.approx(
        max(f / 197e12, nbytes / 819e9))
    assert kernels.gemm_least("fusion(f32[3]{0} %a)", p) is None
