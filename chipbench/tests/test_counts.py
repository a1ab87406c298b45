"""FLOP and byte counts against arithmetic done by hand, at published widths."""
import json
from pathlib import Path

import pytest

from cbench import counts, derive, peaks, tracing
from cbench.loop import Step

CFG = Path(__file__).resolve().parents[1] / "configs"
SL = json.loads((CFG / "stablelm-3b.json").read_text())["model"]
MC = json.loads((CFG / "minicpm3-4b.json").read_text())["model"]


def test_stablelm_decode_step_gemms():
    # per layer at B = 12: q, k, v, o are 2*12*2560*2560 = 157,286,400 each;
    # gate, up, down are 2*12*2560*6912 = 424,673,280 each:
    # 1,903,165,440 a layer, 60,901,294,080 for 32; the unembedding adds
    # 2*12*2560*50304 = 3,090,677,760.
    calls = counts.gemm_calls(SL, 12, 12)
    assert len(calls) == 32 * 7 + 1
    assert sum(f for f, _ in calls) == 63_991_971_840
    # bytes: weights 32*(4*2560*2560 + 3*2560*6912)*2 + 2560*50304*2,
    # activations in and out per call, f32 logits out.
    w = 32 * (4 * 2560 * 2560 + 3 * 2560 * 6912) * 2 + 2560 * 50304 * 2
    act = 32 * 12 * (4 * (2560 + 2560) + 2 * (2560 + 6912) + (6912 + 2560)) * 2
    act += 12 * 2560 * 2 + 12 * 50304 * 4
    assert sum(b for _, b in calls) == w + act == 5_372_692_480


def test_stablelm_prefill_counts():
    # 512 tokens: 512 * 32 * 2 * (4*2560^2 + 3*2560*6912) = 2,598,455,214,080
    # plus one logits row, 2*2560*50304 = 257,556,480
    calls = counts.gemm_calls(SL, 512, 1)
    assert sum(f for f, _ in calls) == 2_598_455_214_080 + 257_556_480
    # flash: 32 heads * 512 * 513 * (80 + 80) FLOPs a layer; q, k, v, o bf16
    fl, by = counts.flash_attention_calls(SL, 512)[0]
    assert (fl, by) == (32 * 512 * 513 * 160, 4 * 512 * 32 * 80 * 2)
    assert fl == 1_344_798_720


def test_stablelm_decode_attention_live_rows():
    # lengths 100 and 200: 300 live rows of K and V, 32 heads x 80, bf16,
    # plus q in and out for 2 slots: 2*(2*300*2560 + 2*2*2560) bytes
    fl, by = counts.decode_attention_calls(SL, [100, 200])[0]
    assert fl == 4 * 300 * 32 * 80 == 3_072_000
    assert by == 2 * (2 * 300 * 2560 + 2 * 2 * 2560) == 3_092_480


def test_minicpm3_counts():
    # per layer at B = 16: q_down 2*16*2560*768, q_up 2*16*768*3840,
    # kv_down 2*16*2560*288, o 2*16*2560*2560, FFN 3*2*16*2560*6400
    per = 2 * 16 * (2560 * 768 + 768 * 3840 + 2560 * 288 + 2560 * 2560
                    + 3 * 2560 * 6400)
    assert sum(f for f, _ in counts.gemm_calls(MC, 16, 16)) == \
        62 * per + 2 * 16 * 2560 * 73448 == 127_751_290_880
    # one slot of 1,000 rows: scores over 288 latent+rope channels and
    # values over the 256 latent ones, 40 heads; keys 288 + values 256 bf16
    fl, by = counts.decode_attention_calls(MC, [1000])[0]
    assert fl == 2 * 40 * 1000 * 288 + 2 * 40 * 1000 * 256 == 43_520_000
    assert by == 2 * (1000 * 544 + 40 * 544) == 1_131_520
    fl, by = counts.flash_attention_calls(MC, 2048)[0]
    assert fl == 40 * 2048 * 2049 * (96 + 64)
    # decode model FLOPs at 3,000 rows: projections + FFN as above at one
    # row, the absorbed up-projections 2*40*256*(64+64), the latent
    # attention 2*40*3000*(288+256), and the unembedding
    one = per // 16 + 2 * 40 * 256 * 128 + 2 * 40 * 3000 * 544
    assert counts.model_flops_decode(MC, 3000) == 62 * one + 2 * 2560 * 73448


def _trace_with(events, window=(0.0, 1.0)):
    return tracing.Trace(window=window, ops={0: events}, modules={0: []},
                         host=[tracing.Ev(tracing.WINDOW_SPAN, *window)])


def test_share_at_max_len_would_pass_100_where_live_rows_do_not():
    """A kernel that reads only live pages takes about the live rows'
    least time; charged with max_len rows it would read above 100%."""
    p = peaks.chip_peaks("TPU v5 lite")
    lengths = [200] * 12
    live = counts.least_seconds(counts.decode_attention_calls(SL, lengths), p)
    spent = 1.25 * live                   # the kernel at 80% of its roofline
    step = Step(0.1, 0.2, 12, [], lengths, traced=True)
    ctx = derive.Context(model=SL, mix={"max_slots": 12}, reqs=[], steps=[step],
                         window=(0.0, 1.0), setup_s=0.0, compiles_in_window=0,
                         peaks=p, trace=_trace_with(
                             [tracing.Ev("decode_attention.3", 0.1, 0.1 + spent)]))
    share = derive.kernel_roofline(
        ctx, r"decode_attention(\.\d+)?",
        lambda st: counts.decode_attention_calls(SL, st.decode_lens))
    assert share == pytest.approx(80.0)
    at_max_len = counts.least_seconds(
        counts.decode_attention_calls(SL, [1024] * 12), p)
    assert 100.0 * at_max_len / spent > 100.0
