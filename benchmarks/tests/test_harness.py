"""The shared arithmetic: trace reduction, schedule, percentiles, last line."""

import io
import json
from contextlib import redirect_stdout

from harness import loadgen, result, stats, trace

CHAT = {"rate_rps": 8, "mix_seed": 24, "ramp_seconds": 5, "vocab_size": 50257,
        "max_total_tokens": 1024,
        "prompt_tokens": {"median": 160, "sigma": 0.9, "low": 16, "high": 768},
        "output_tokens": {"median": 96, "sigma": 0.7, "low": 8, "high": 256}}


def hand_built_trace():
    """Two devices, times in ns.  Device 0: program A runs 0-100 with ops
    at 0-40 and 50-100 (a 10 ns gap inside A), then nothing until program B
    at 300-400 with one all-reduce at 300-360 and a fusion overlapping it
    at 340-400, and an asynchronous all-reduce in flight 350-380.  Device 1:
    one op 0-400."""
    return {
        "/device:TPU:0": {
            "ops": [("fusion.1", 0, 40), ("fusion.2", 50, 50),
                    ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} "
                     "%fusion.2), replica_groups={}", 300, 60),
                    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} "
                     "%all-reduce.3), kind=kLoop", 340, 60)],
            "modules": [("jit_a(123)", 0, 100), ("jit_b(456)", 300, 100)],
            "async": [("%all-reduce-start.7 = f32[8]{0} all-reduce-start("
                       "f32[8]{0} %fusion.2)", 350, 30)]},
        "/device:TPU:1": {
            "ops": [("fusion.9", 0, 400)],
            "modules": [("jit_a(123)", 0, 400)]},
    }


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert trace.covered([(0, 10), (2, 3), (20, 25)]) == 15


def test_trace_reduction_busy_idle_names_and_gaps():
    r = trace.reduce(hand_built_trace())
    ns = 1e-9
    assert r["devices"] == 2
    assert r["window_s"] == 400 * ns
    # device 0 busy 40 + 50 + 100 = 190, device 1 busy 400: mean 295
    assert abs(r["busy_s"] - 295 * ns) < 1e-15
    # all-reduce 300-360 and its in-flight span 350-380: 80 on one of two
    assert abs(r["collective_s"] - 40 * ns) < 1e-15
    assert abs(r["by_name"]["fusion.1"] - 50 * ns) < 1e-15  # (40 + 60) / 2
    gaps = r["gaps"]
    assert abs(gaps["unattributed, inside jit_a"] - 5 * ns) < 1e-15
    assert abs(gaps["unattributed, after jit_a"] - 100 * ns) < 1e-15
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == "fusion.9"
    assert b["idle_gaps"][0][0] == "unattributed, after jit_a"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_trace_reduction_of_no_device_is_empty():
    assert trace.reduce({}) == {}


def test_schedule_is_a_pure_function_of_seed_and_parameters():
    a = loadgen.schedule(CHAT, 3000000001, 30)
    assert a == loadgen.schedule(CHAT, 3000000001, 30)
    b = loadgen.schedule(CHAT, 7, 30)
    assert a != b
    # every seed offers the same set of sizes, in another order
    size = lambda s: sorted((len(r["tokens"]), r["max_new_tokens"])
                            for r in s if r["counted"])
    assert size(a) == size(b)
    assert sum(r["counted"] for r in a) == 240
    assert sum(not r["counted"] for r in a) == 40
    assert all(0 <= r["due"] < 30 for r in a if r["counted"])
    assert all(-5 <= r["due"] < 0 for r in a if not r["counted"])


def test_schedule_clips_lengths_to_the_context():
    for r in loadgen.schedule(CHAT, 1, 60):
        assert 16 <= len(r["tokens"]) <= 768
        assert 8 <= r["max_new_tokens"] <= 256
        assert len(r["tokens"]) + r["max_new_tokens"] <= 1024
    tight = dict(CHAT, max_total_tokens=300,
                 prompt_tokens=dict(CHAT["prompt_tokens"], high=290))
    clipped = loadgen.schedule(tight, 1, 60)
    assert all(len(r["tokens"]) + r["max_new_tokens"] <= 300
               and r["max_new_tokens"] >= 1 for r in clipped)
    assert any(len(r["tokens"]) + r["max_new_tokens"] == 300
               for r in clipped)


def test_percentile_is_nearest_rank_and_carries_its_count():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == (95, 100)
    assert stats.percentile(values, 50) == (50, 100)
    assert stats.percentile([3.0, 1.0, 2.0], 95) == (3.0, 3)
    assert stats.percentile([5.0], 95) == (5.0, 1)
    assert stats.percentile([], 95) == (None, 0)
    assert stats.summary([1, 2, 3, 4])["n"] == 4


def test_last_line_has_exactly_the_contract_keys():
    out = io.StringIO()
    with redirect_stdout(out):
        result.log("an earlier line")
        result.emit(True, 3, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
                    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                     "memory_peak_bytes": 5})
    last = json.loads(out.getvalue().strip().split("\n")[-1])
    assert sorted(last) == sorted(result.LAST_LINE_KEYS)
    out = io.StringIO()
    with redirect_stdout(out):
        result.emit(True, 3, 0, {}, {}, {"device_ops": [], "idle_gaps": []})
    last = json.loads(out.getvalue().strip().split("\n")[-1])
    assert sorted(last) == sorted(result.LAST_LINE_KEYS + ("breakdown",))
