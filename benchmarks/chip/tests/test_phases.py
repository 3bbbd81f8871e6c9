"""Phases and scopes in a trace (``phases.py``): innermost-phase idle
attribution and the decision plane's share of the decode program."""
import gzip
import json

import pytest

from benchmarks.chip import harness, phases, trace
from benchmarks.chip.metrics import device_idle_share

DATA = harness.HERE / "tests" / "data"
MS = 1_000_000


def device(busy_ms, ops=()):
    """A device whose ops run over ``busy_ms`` ([start, end] pairs, ms)."""
    return {"name": "/device:TPU:0",
            "modules": [["jit__decode_impl(1)", 0, 100 * MS]],
            "ops": list(ops) or [[f"%op.{i} = f32[] op()", s * MS,
                                  (e - s) * MS]
                                 for i, (s, e) in enumerate(busy_ms)]}


def reduced(devices, spans, window=(0, 100)):
    raw = {"host": [["bench.window", window[0] * MS,
                     (window[1] - window[0]) * MS],
                    ["engine.step", window[0] * MS,
                     (window[1] - window[0]) * MS]],
           "devices": devices}
    red = trace.reduce_raw(raw)
    return phases.with_phases(
        red, [[n, s * MS, (e - s) * MS] for n, s, e in spans])


def idle_share(red):
    from types import SimpleNamespace
    return device_idle_share.read(SimpleNamespace(red=red))


def test_idle_goes_to_the_innermost_open_phase():
    # busy 0-10, 30-40, 70-80; idle 10-30, 40-70, 80-100
    red = reduced([device([(0, 10), (30, 40), (70, 80)])], [
        ("obs.schedule", 5, 12),
        ("obs.prefill", 12, 60),
        ("obs.admit_decide", 15, 25),
        ("obs.admit_fetch", 45, 55),
        ("obs.dispatch", 60, 65)])
    by = phases.idle_by_phase(red)
    ms = {k: round(v * 1e3, 9) for k, v in by.items()}
    assert ms == {"obs.schedule": 2, "obs.admit_decide": 10,
                  "obs.prefill": 3 + 5 + 5 + 5, "obs.admit_fetch": 10,
                  "obs.dispatch": 5, "none": 5 + 20}
    sh = phases.idle_shares(red)
    assert sh == pytest.approx({"admit": 38.0, "step_host": 7.0,
                                "outside": 25.0})
    assert sum(sh.values()) == pytest.approx(idle_share(red))


def test_phases_crossing_the_window_are_clipped():
    # window 20-80; busy 30-40 only; a phase from before the window to 50,
    # one from 70 to after it
    red = reduced([device([(30, 40)])],
                  [("obs.drain", 0, 50), ("obs.commit", 70, 120)],
                  window=(20, 80))
    assert red["spans"][0][1:] == [20 * MS, 30 * MS]
    ms = {k: round(v * 1e3, 9) for k, v in phases.idle_by_phase(red).items()}
    assert ms == {"obs.drain": 10 + 10, "none": 20, "obs.commit": 10}
    # a clipped prefill is not a whole admission
    red = reduced([device([(30, 40)])], [("obs.prefill", 10, 30),
                                         ("obs.prefill", 40, 46)],
                  window=(20, 80))
    assert phases.admit_ms(red) == pytest.approx(6.0)


def test_two_devices_average():
    a = device([(0, 50)])
    b = dict(device([(0, 100)]), name="/device:TPU:1")
    red = reduced([a, b], [("obs.drain", 40, 100)])
    ms = {k: round(v * 1e3, 9) for k, v in phases.idle_by_phase(red).items()}
    assert ms == {"obs.drain": 25.0}      # 50 ms on one, 0 on the other
    assert phases.idle_shares(red)["step_host"] == pytest.approx(25.0)
    assert idle_share(red) == pytest.approx(25.0)


def test_a_program_without_phases_reads_nothing():
    red = reduced([device([(0, 50)])], [])
    assert phases.idle_shares(red) is None
    assert phases.admit_ms(red) is None
    assert phases.decision_share_of_decode(red) is None
    red = trace.reduce_raw({"host": [["bench.window", 0, 100 * MS]],
                            "devices": [device([(0, 50)])]})
    assert phases.readings(red)["idle_in_admit_share"] is None


HLO = """\
ENTRY %main.9 (p: bf16[8]) -> bf16[8] {
  %copy.3 = bf16[8] copy(%p)
  %fusion.1 = bf16[8] fusion(%copy.3), kind=kLoop, calls=%f, \
metadata={op_name="jit(_decode_impl)/forward/while/body/dot_general" \
stack_frame_id=3}
  %sort.2 = (bf16[8], s32[8]) sort(%fusion.1), dimensions={0}, \
metadata={op_name="jit(_decode_impl)/decision/jit(shvs)/sort"}
  ROOT %add.4 = bf16[8] add(%sort.2, %p), metadata={op_name="jit(_decode_impl)/add"}
}
"""


def test_hlo_scopes_read_each_instructions_op_name():
    assert phases.hlo_scopes(HLO) == {
        "fusion.1": "jit(_decode_impl)/forward/while/body/dot_general",
        "sort.2": "jit(_decode_impl)/decision/jit(shvs)/sort",
        "add.4": "jit(_decode_impl)/add"}


def test_decision_share_of_decode():
    ops = [["%copy.3 = bf16[8] copy(...)", 0, 10 * MS],
           ["%fusion.1 = bf16[8] fusion(...)", 10 * MS, 50 * MS],
           ["%sort.2 = bf16[8] sort(...)", 60 * MS, 30 * MS],
           ["%add.4 = bf16[8] add(...)", 90 * MS, 10 * MS]]
    red = trace.reduce_raw({"host": [["bench.window", 0, 100 * MS]],
                            "devices": [device([], ops)]})
    red = phases.with_phases(red, [],
                             {"jit__decode_impl": phases.hlo_scopes(HLO)})
    assert phases.decision_share_of_decode(red) == pytest.approx(30.0)
    shares = phases.scope_shares(red)
    assert shares == pytest.approx({"forward": 50.0, "decision": 30.0,
                                    "unscoped": 20.0, "covered": 90.0})
    # a program whose ops carry no decision scope (as before the scopes
    # were added) reads nothing
    red["scopes"] = {"jit__decode_impl": {"fusion.1": "jit(_decode_impl)/x"}}
    assert phases.decision_share_of_decode(red) is None


def recorded(pattern="*.phases.json.gz"):
    files = sorted(DATA.glob(pattern))
    assert files, f"no trace {pattern} recorded on the chip under tests/data"
    with gzip.open(files[0], "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("pattern", ["*.trace.json.gz", "*.phases.json.gz"])
def test_phases_leave_the_reduction_as_it_was(pattern):
    """Every key of the benchmark's own reduction, and its breakdown line,
    read the same with the phases and scopes beside them."""
    raw = recorded(pattern)
    plain = trace.reduce_raw({"host": raw["host"],
                              "devices": raw["devices"]})
    red = phases.with_phases(trace.reduce_raw(raw), raw.get("spans", []),
                             raw.get("scopes"))
    assert {k: red[k] for k in plain} == plain
    assert trace.breakdown(red) == trace.breakdown(plain)
    assert set(red) - set(plain) == {"spans", "scopes"}


def test_recorded_chip_trace_with_phases():
    raw = recorded()
    red = phases.with_phases(trace.reduce_raw(raw), raw["spans"],
                             raw["scopes"])
    r = phases.readings(red)
    assert r["phases"] > 0 and r["admit_ms"] > 0
    sh = phases.idle_shares(red)
    assert sum(sh.values()) == pytest.approx(idle_share(red))
    assert 0 < r["decision_share_of_decode"] < 100
    kinds = {n for n, _, _ in red["spans"]}
    assert {"obs.prefill", "obs.admit_decide", "obs.admit_insert",
            "obs.admit_fetch", "obs.dispatch", "obs.drain",
            "obs.commit", "obs.schedule"} <= kinds
