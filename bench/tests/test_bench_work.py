"""Work counts and peaks: the numbers every roofline share divides by.
The per-call counts are `bench/work.py`'s, a Qwen2 model's sizes
`bench/arch/qwen2.py`'s."""
import pytest

from bench import model, peaks, work
from bench.arch import qwen2


@pytest.mark.parametrize("name,gb", [("qwen2-7b-14L", 7.62),
                                     ("qwen1.5-0.5b", 0.93)])
def test_decode_streams_the_weights(name, gb):
    shape = qwen2.Shape.from_hf(model.load_config(name))
    assert round(2 * shape.streamed_params() / 1e9, 2) == gb


def test_qwen2_7b_layer_and_head_by_hand():
    s = qwen2.Shape.from_hf(model.load_config("qwen2-7b-14L"))
    # q 3584x3584, k and v 3584x512, o 3584x3584, gate/up/down 3584x18944
    assert s.layer_params() == (2 * 3584 * 3584 + 2 * 3584 * 512
                                + 3 * 3584 * 18944) == 233_046_016
    assert s.head_params() == 152064 * 3584
    assert s.kv_bytes_per_token() == 2 * 14 * 4 * 128 * 2 == 28_672


def test_gpp_call_by_hand():
    w = work.gpp_call(16, 3584, 18944)
    assert w.flops == 2 * 16 * 3584 * 18944 == 2_172_649_472
    # weights, x and y once each, bf16
    assert w.bytes == 2 * (3584 * 18944 + 16 * 3584 + 16 * 18944)
    assert work.gpp_call(1, 8, 4, bias=True).bytes == 2 * (32 + 8 + 4) + 16


def test_paged_attn_call_by_hand():
    # two lanes (contexts 100 and 1), 28 heads over 4 KV heads of 128
    w = work.paged_attn_call([100, 1], 1, 28, 4, 128)
    assert w.flops == 4 * 28 * 128 * 101 == 1_447_936
    assert w.bytes == 2 * (2 * 101 * 4 * 128 + 2 * 2 * 28 * 128) == 235_520
    # a prefill chunk of 4 rows ending a 10-token context sees 7+8+9+10 keys
    c = work.paged_attn_call([10], 4, 2, 2, 8)
    assert c.flops == 4 * 2 * 8 * (7 + 8 + 9 + 10)


def test_least_time_is_the_larger_bound():
    w = work.Work(flops=197e12, bytes=819e9 / 2)
    assert w.least_s(197e12, 819e9) == 1.0
    assert work.Work(1.0, 819e9).least_s(197e12, 819e9) == 1.0


def test_peaks_known_and_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
