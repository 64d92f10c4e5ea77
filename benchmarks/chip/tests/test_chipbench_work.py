"""Work counts match hand computation at olmo-1b and phi-4-mini widths."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

from chipbench import peaks, work  # noqa: E402

OLMO = work.dims(json.loads((HERE / "configs/olmo1b.json").read_text()))
PHI = work.dims(json.loads((HERE / "configs/phi4mini.json").read_text()))


def test_dims_read_the_published_keys():
    assert OLMO == work.Dims(16, 2048, 16, 16, 128, 8192, 50304, 2)
    assert PHI == work.Dims(32, 3072, 24, 8, 128, 8192, 200064, 2)


@pytest.mark.parametrize("c,per_layer", [
    # 2 * (q + o: 2*d*H*hd, k + v: 2*d*KH*hd, swiglu: 3*d*f)
    (OLMO, 2 * (2 * 2048 * 2048 + 2 * 2048 * 2048 + 3 * 2048 * 8192)),
    (PHI, 2 * (2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192)),
], ids=["olmo", "phi4"])
def test_matmul_flops_per_token(c, per_layer):
    assert work.matmul_flops_per_token(c) == c.layers * per_layer


def test_decode_lane_attention_work_by_hand():
    # one token at context 1000: QK and PV, 2 flops a MAC each
    f, b = work.lane_attn(PHI, 1, 1000)
    assert f == 4 * 24 * 128 * 1000
    # K and V of 1000 tokens over 8 kv heads, q and out of 24 heads, bf16
    assert b == 2 * 1000 * 8 * 128 * 2 + 2 * 24 * 128 * 2


def test_prefill_lane_attends_causally():
    # 64 tokens ending at context 128 see 65..128 positions
    assert work.lane_ctx_sum(64, 128) == sum(range(65, 129))
    f, _ = work.lane_attn(OLMO, 64, 128)
    assert f == 4 * 16 * 128 * sum(range(65, 129))


def test_attention_least_time_takes_the_larger_bound_per_call():
    pf, bw = peaks.peaks("TPU v5 lite")
    decode = [(1, 1200)] * 18                 # bytes bound
    prefill = [(64, 1024)] * 18               # flops bound at olmo widths
    t, share = work.attn_least_s(OLMO, [decode], pf, bw)
    f, b = work.step_attn(OLMO, decode)
    assert t == pytest.approx(16 * b / bw) and share == 1.0
    t2, share2 = work.attn_least_s(OLMO, [prefill], pf, bw)
    f2, b2 = work.step_attn(OLMO, prefill)
    assert t2 == pytest.approx(16 * max(f2 / pf, b2 / bw))
    t3, _ = work.attn_least_s(OLMO, [decode, prefill], pf, bw)
    assert t3 == pytest.approx(t + t2)


def test_model_flops_add_head_per_emitted_token():
    lanes = [[(1, 10)], [(2, 12)]]
    per_tok = work.matmul_flops_per_token(OLMO)
    attn = 16 * 4 * 16 * 128 * (10 + 11 + 12)
    assert work.model_flops(OLMO, lanes, 2) == (
        3 * per_tok + attn + 2 * 2 * 2048 * 50304)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
