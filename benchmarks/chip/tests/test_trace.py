"""The reduction from trace events to metrics."""
import gzip
import json
from types import SimpleNamespace

import pytest

from benchmarks.chip import harness, trace
from benchmarks.chip.peaks import peaks_for

DATA = harness.HERE / "tests" / "data"
MS = 1_000_000


def synthetic():
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["engine.step", 0, 60 * MS],
                 ["client.idle", 60 * MS, 40 * MS]],
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit__decode_impl(123)", 10 * MS, 30 * MS],
                        ["jit__prefill_impl(9)", 70 * MS, 10 * MS]],
            "ops": [["%while.1 = (...) while(...)", 10 * MS, 30 * MS],
                    ["%fusion.2 = f32[] fusion()", 12 * MS, 10 * MS],
                    ["%copy.3 = bf16[] copy()", 25 * MS, 5 * MS],
                    ["%fusion.9 = f32[] fusion()", 70 * MS, 10 * MS],
                    ["%late = f32[] x()", 150 * MS, 1 * MS]]}],
    }


def test_busy_union_self_time_and_modules():
    red = trace.reduce_raw(synthetic())
    assert trace.window_s(red) == pytest.approx(0.1)
    assert trace.busy_s(red) == pytest.approx(0.04)
    assert trace.module_calls(red, "jit__decode_impl") == [pytest.approx(0.03)]
    ops = dict(trace.top_ops(red))
    assert ops["jit__decode_impl/while.1"] == pytest.approx(0.015)
    assert ops["jit__decode_impl/fusion.2"] == pytest.approx(0.010)
    assert ops["jit__prefill_impl/fusion.9"] == pytest.approx(0.010)
    assert "late" not in str(ops)


def test_idle_gaps_named_by_the_host_span_over_them():
    gaps = dict(trace.idle_gaps(trace.reduce_raw(synthetic())))
    # idle 0-10 and 40-60 ms under engine.step, 60-70 and 80-100 ms under
    # client.idle
    assert gaps["engine.step"] == pytest.approx(0.03)
    assert gaps["client.idle"] == pytest.approx(0.03)
    assert "none" not in gaps


def test_no_window_span_is_an_error():
    raw = synthetic()
    raw["host"] = raw["host"][1:]
    with pytest.raises(RuntimeError):
        trace.reduce_raw(raw)


def recorded():
    files = sorted(DATA.glob("*.trace.json.gz"))
    assert files, "no trace recorded on the chip under tests/data"
    with gzip.open(files[0], "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_reduces():
    red = trace.reduce_raw(recorded())
    assert red["devices"] and red["devices"][0]["name"] == "/device:TPU:0"
    w = trace.window_s(red)
    assert 0 < trace.busy_s(red) <= w
    assert trace.module_calls(red, "jit__decode_impl")
    assert len(trace.top_ops(red)) == 10
    for name, sec in trace.top_ops(red) + trace.idle_gaps(red):
        assert sec >= 0


def test_readers_on_the_recorded_trace():
    from benchmarks.chip.metrics import (decode_device_ms, decode_roofline,
                                         device_idle_share)
    red = trace.reduce_raw(recorded())
    cfg = harness.load_json(harness.HERE / "configs"
                            / "qwen3-8b-pp4-last.json")
    r = SimpleNamespace(cfg=cfg, peaks=peaks_for("TPU v5 lite"), batch=64,
                        red=red, records=[], compiles=0,
                        steps=[SimpleNamespace(decode_ctx=[400] * 64,
                                               admitted_prompts=[])])
    ms = decode_device_ms.read(r)
    assert ms > 0
    assert 0 < decode_roofline.read(r) <= 100
    assert 0 <= device_idle_share.read(r) < 100
