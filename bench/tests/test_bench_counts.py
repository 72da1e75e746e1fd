"""The yardstick's arithmetic: trace reduction, operation and byte counts,
and the peak table.

The trace reduction is checked on events built by hand, whose busy time,
kernel time and idle gaps are known, and on a short trace recorded on a
TPU v5e by a traced run of `granite8b.scan_cold` (data/trace_small.json.gz:
its device ops, with custom calls' signatures, and benchmark spans; 2,701
events, the first 12.5 s of the window, the window span cut to them).
The counts are checked against hand counts at the planted small model's
widths (d_model 32, 2 heads of 16, d_ff 64, vocab 256, 2 layers)."""
import gzip
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from sbench import flops as FL  # noqa: E402
from sbench import trace as TRC  # noqa: E402
from sbench.peaks import peaks_for  # noqa: E402
from sbench.harness import load_json, load_module  # noqa: E402

ROOF = load_module(os.path.join(BENCH, "metrics",
                                "decode_query_attention_roofline.py"),
                   "metric_decode_query_attention_roofline")
GRANITE = load_json(os.path.join(BENCH, "configs", "granite-8b.json"))

DATA = os.path.join(BENCH, "tests", "data")
PLANTED_SM = {"hidden_size": 32, "intermediate_size": 64,
              "num_attention_heads": 2, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256}


def ev(plane, name, start, dur, **kw):
    return {"plane": plane, "line": "x", "name": name, "start_ns": start,
            "dur_ns": dur, **kw}


# a custom call's HLO text as a TPU v5e trace gives it (cut by the trace
# at 400 of its 479 characters here): the decode kernel at granite-8b's
# widths, batch 8, cache 256
HLO_DECODE = (
    '%_lambda_.1 = bf16[8,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} '
    'custom-call(s32[8]{0:T(128)} %l.1, s32[1]{0:T(128)} %constant.3,'
    ' bf16[8,8,4,128]{3,2,1,0:T(4,128)(2,1)} %bitcast.5, '
    'bf16[8,256,8,128]{3,2,1,0:T(8,128)(2,1)} %k.1, '
    'bf16[8,256,8,128]{3,2,1,0:T(8,128)(2,1)} %v.1), '
    'custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[8]{0}, s32[1]{0}, '
    'bf16[8,8,4,128]{3,2,1,0}, bf16[8,256,8,128]{')
DECODE = ("tpu_custom_call(s32[64],s32[1],bf16[64,8,4,128],"
          "bf16[64,640,8,128],bf16[64,640,8,128])->bf16[64,8,4,128]")
OTHER = "tpu_custom_call(bf16[64,640,8,128])->f32[64,640,8]"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
EVENTS = [
    ev(HOST, "bench.window", 0, 1000),
    ev(HOST, "bench.run", 0, 1000),
    ev(HOST, "bench.load_batch", 100, 300),       # open over [100, 400)
    ev(DEV, "%closed_call.1 custom-call", 0, 100, call=DECODE),
    ev(DEV, "fusion.1", 50, 100),                 # overlaps the kernel
    ev(DEV, "%closed_call.1 custom-call", 400, 200, call=DECODE),
    ev(DEV, "%custom-call.2 custom-call", 420, 50, call=OTHER),
    ev(DEV, "fusion.2", 900, 200),                # runs past the window
    ev(DEV, "fusion.3", 1200, 50),                # outside the window
]


def test_busy_time_is_the_union_of_device_ops():
    r = TRC.reduce_trace(EVENTS, TRC.window_of(EVENTS))
    # [0,150) + [400,600) + [900,1000) inside the 1000 ns window
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["chips"] == 1


def test_kernel_time_and_top_ops():
    r = TRC.reduce_trace(EVENTS, TRC.window_of(EVENTS))
    # by the decode kernel's signature: the other custom call is not counted
    pattern = ROOF.kernel_pattern(GRANITE)
    assert TRC.kernel_time(r, pattern) == pytest.approx(300e-9)
    assert TRC.kernel_time(r, r"tpu_custom_call\(s32\[9\]") is None
    assert r["device_ops"][0] == ["%closed_call.1 custom-call",
                                  pytest.approx(300e-9)]
    assert [n for n, _ in r["device_ops"]] == [
        "%closed_call.1 custom-call", "fusion.1", "fusion.2",
        "%custom-call.2 custom-call"]


def test_call_signature_of_a_traced_kernel():
    sig = TRC.call_signature(HLO_DECODE)
    assert sig == ("tpu_custom_call(s32[8],s32[1],bf16[8,8,4,128],"
                   "bf16[8,256,8,128],bf16[8,256,8,128])->bf16[8,8,4,128]")
    assert re.match(ROOF.kernel_pattern(GRANITE), sig)
    # other widths, or another kernel, do not match
    assert not re.match(ROOF.kernel_pattern(dict(GRANITE, head_dim=64)),
                        sig)
    assert not re.match(ROOF.kernel_pattern(GRANITE), OTHER)
    assert TRC.call_signature(
        "%fusion.110 = bf16[64,14336]{1,0} fusion(bf16[64,4096]{1,0} "
        "%p), kind=kLoop") is None


def test_idle_gaps_are_named_by_the_open_host_span():
    r = TRC.reduce_trace(EVENTS, TRC.window_of(EVENTS))
    gaps = dict(r["idle_gaps"])
    # [150,400) under load_batch (innermost), [600,900) under run only
    assert gaps == {"bench.load_batch": pytest.approx(250e-9),
                    "bench.run": pytest.approx(300e-9)}


def test_union_merges_overlaps():
    assert TRC.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_recorded_trace():
    with gzip.open(os.path.join(DATA, "trace_small.json.gz"), "rt") as f:
        events = json.load(f)
    r = TRC.reduce_trace(events, TRC.window_of(events))
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    times = [s for _, s in r["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert all(n.startswith("bench.") or n == "no span"
               for n, _ in r["idle_gaps"])
    idle = sum(s for _, s in TRC.reduce_trace(
        events, TRC.window_of(events), top=10 ** 6)["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-6)
    pattern = ROOF.kernel_pattern(GRANITE)
    assert TRC.kernel_time(r, pattern) > 0
    # the flushes' other custom calls (buffer allocations) are not counted
    assert any(not re.match(pattern, c) for c in r["call_time"])


def test_counts_match_hand_counts_at_planted_widths():
    # per layer: q, k, v, o 32x32 each, and 3 x 32x64 in the MLP;
    # plus the 32x256 head; 2 FLOPs per multiply-add
    assert FL.matmul_flops_per_token(PLANTED_SM) == 2 * (
        2 * (4 * 32 * 32 + 3 * 32 * 64) + 32 * 256)
    # QK^T and PV over 100 cached positions and the query itself,
    # 2 heads of 16, 2 layers; float32 queries and outputs
    flops, qo = FL.attention_work(PLANTED_SM, 100)
    assert flops == 2 * 2 * 2 * 16 * 101 * 2
    assert qo == 2 * 2 * 16 * 4 * 2
    # bf16 K and V: 2 layers x 2 heads x 16 x 2 bytes, twice
    assert FL.kv_bytes_per_token(PLANTED_SM) == 2 * 2 * 2 * 16 * 2
    # int8 K and V with one float32 scale per (position, head) each
    assert FL.kv_bytes_per_token(PLANTED_SM, quant=True) == 2 * 2 * 2 * (
        16 + 4)


def test_roofline_bound_takes_the_larger_time():
    b = FL.decode_bound_s(197e12, 819e9, 197e12, 819e9)
    assert b["bound_s"] == pytest.approx(1.0) and b["dominant"] == "memory"
    b = FL.decode_bound_s(394e12, 819e9, 197e12, 819e9)
    assert b["bound_s"] == pytest.approx(2.0) and b["dominant"] == "compute"


def test_peaks_are_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bw) == (197e12, 393e12, 819e9)
    assert "Google Cloud" in p.source
    with pytest.raises(ValueError):
        peaks_for("cpu")
