"""Telemetry-plane suite (DESIGN.md §17): typed step records, the
span tracer / flight recorder, Chrome-trace export, the metrics
registry's Prometheus exposition, and the live gateway endpoints.

Marked ``obs`` and excluded from tier-1 (the integration tests boot real
engines and sockets); CI runs the suite in its own step.
"""
import asyncio
import json
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import SHVSConfig
from repro.core.autotune import CONTROLLER_STREAMS, DecisionPlaneController
from repro.engine import (Engine, EngineConfig, PipelineConfig,
                          PipelineEngine, Request)
from repro.gateway import GatewayServer, ReplicaFleet
from repro.gateway.client import request_json, stream_completion
from repro.gateway.smoke import PROMPTS, VOCAB, smoke_model
from repro.models.model import Model
from repro.obs import (DEFAULT_MS_BUCKETS, NULL_SPAN, SPAN_KINDS,
                       CycleRecord, MetricsRegistry, StepRecord, StepTracer,
                       Telemetry, chrome_trace, chrome_trace_events,
                       render_registries, write_chrome_trace)

pytestmark = pytest.mark.obs

_CACHE: dict = {}


def _params():
    if "params" not in _CACHE:
        _CACHE["params"] = Model(smoke_model()).init(jax.random.PRNGKey(0))
    return _CACHE["params"]


def _sampling(seed: int):
    from repro.config import SamplingConfig
    return SamplingConfig(temperature=0.9, top_k=40, top_p=0.95,
                          repetition_penalty=1.1, seed=seed)


def _requests(n: int, max_new: int = 8, base_seed: int = 300):
    return [Request(request_id=100 + i,
                    prompt=[(7 * i + k) % (VOCAB - 1) + 1
                            for k in range(5 + i)],
                    max_new_tokens=max_new,
                    sampling=_sampling(base_seed + i))
            for i in range(n)]


# -- StepRecord / CycleRecord -------------------------------------------------

def test_step_record_mapping_duck_typing():
    host = StepRecord(step=3, batch=2, accept_rate=0.5, stall_ms=1.25,
                      sampler_ms=0.5, transfer_ms=0.75)
    dev = StepRecord(step=4, batch=2, accept_rate=0.9)
    # the dict convention the old consumers rely on: present iff not None
    assert "stall_ms" in host and host["stall_ms"] == 1.25
    assert "stall_ms" not in dev
    with pytest.raises(KeyError):
        dev["stall_ms"]
    assert dev.get("stall_ms", -1.0) == -1.0
    assert host.is_host and not dev.is_host
    assert "nonexistent_field" not in dev
    d = host.as_dict()
    assert d["stall_ms"] == 1.25 and "bubble_frac" not in d
    assert set(host.keys()) == set(d)


def test_step_record_validation():
    with pytest.raises(ValueError):
        StepRecord(step=-1, batch=0)
    with pytest.raises(ValueError):
        StepRecord(step=0, batch=1, stall_ms=-0.5)
    with pytest.raises(ValueError):
        StepRecord(step=0, batch=1, sampler_ms=float("nan"))
    with pytest.raises(ValueError):
        StepRecord(step=0, batch=1, sampler_mode="disaggregated")
    # queue_delay_ms may be NaN ("arrivals carry no stamps")
    r = StepRecord(step=0, batch=1, queue_delay_ms=float("nan"))
    assert math.isnan(r.queue_delay_ms)


def test_controller_streams_covers_every_stream_with_nan_fill():
    rec = StepRecord(step=1, batch=3, alpha_mean=0.4, stall_ms=2.0,
                     queue_depth=5.0)
    streams = rec.controller_streams()
    assert set(streams) == set(CONTROLLER_STREAMS)
    assert streams["stall_ms"] == 2.0 and streams["batch"] == 3.0
    assert math.isnan(streams["sampler_ms"])      # unset -> NaN, dropped
    assert math.isnan(streams["bubble_frac"])


def test_controller_observe_record_matches_observe():
    a = DecisionPlaneController(mode="device", samplers=2, queue_high=4.0)
    b = DecisionPlaneController(mode="device", samplers=2, queue_high=4.0)
    for step in range(40):
        rec = StepRecord(step=step, batch=4, alpha_mean=0.5,
                         queue_depth=8.0, queue_delay_ms=3.0)
        act_a = a.observe_record(rec)
        act_b = b.observe(**rec.controller_streams())
        assert (act_a is None) == (act_b is None)
        if act_a is not None:
            assert act_a.sampler_mode == act_b.sampler_mode
    assert a.mode == b.mode == "host"     # pressure switched the placement


def test_cycle_record_full_property():
    assert not CycleRecord(cycle=0, busy=[0.1, None]).full
    assert CycleRecord(cycle=1, busy=[0.1, 0.2]).full


# -- tracer / flight recorder -------------------------------------------------

def test_spans_nest_on_one_clock():
    clock_val = [0.0]

    def clock():
        clock_val[0] += 1.0
        return clock_val[0]

    tr = StepTracer(capacity=64, enabled=True, clock=clock)
    with tr.span("forward", name="outer", track="t"):
        with tr.span("commit", name="inner", track="t"):
            pass
    evs = tr.events()
    assert [e.name for e in evs] == ["inner", "outer"]   # inner exits first
    inner, outer = evs
    # nested span lies strictly inside its parent — both stamped on the
    # same injected clock, so no cross-clock skew is possible
    assert outer.ts < inner.ts and inner.end < outer.end
    assert inner.dur >= 0 and outer.dur >= 0


def test_ring_buffer_evicts_oldest():
    tr = StepTracer(capacity=4, enabled=True)
    for k in range(10):
        tr.instant("decision", name=f"d{k}")
    assert len(tr) == 4
    assert [e.name for e in tr.events()] == ["d6", "d7", "d8", "d9"]


def test_disabled_tracer_records_nothing():
    tr = StepTracer(capacity=16, enabled=False)
    assert tr.span("forward") is NULL_SPAN
    assert tr.span("forward") is tr.span("commit")   # one shared no-op CM
    with tr.span("forward", name="x"):
        pass
    tr.add("commit", 0.0, 1.0)
    tr.instant("decision")
    assert len(tr) == 0
    tr.enable()
    tr.instant("decision")
    assert len(tr) == 1


def test_unknown_span_kind_rejected():
    tr = StepTracer(capacity=4, enabled=True)
    with pytest.raises(ValueError, match="unknown span kind"):
        tr.add("fwrward", 0.0, 1.0)
    assert "forward" in SPAN_KINDS and "stage" in SPAN_KINDS


def _profiled(tmp_path, fn):
    """Run ``fn`` under a real ``jax.profiler`` session and return the
    host events named ``obs.*`` it wrote, as (name, start, end, stats)
    sorted by start."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    xplane, = tmp_path.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for line in plane.lines for e in line.events
                    if e.name.startswith("obs.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.mark.parametrize("enabled", [False, True],
                         ids=["disabled", "enabled"])
def test_phase_lands_in_the_profiler_trace(tmp_path, enabled):
    tr = StepTracer(capacity=16, enabled=enabled)
    stamps = []

    def run():
        with tr.phase("dispatch", name="dispatch@7", step=7, rows=3) as ph:
            pass
        stamps.append((ph.t0, ph.t1))

    evs = _profiled(tmp_path, run)
    # the profiler sink always: one annotation, its args as metadata
    assert [(n, st["step"], st["rows"]) for n, _, _, st in evs] == \
        [("obs.dispatch", 7, 3)]
    # the ring only when enabled, on the stamps the phase exposes
    t0, t1 = stamps[0]
    assert t0 <= t1
    if enabled:
        ev, = tr.events()
        assert (ev.kind, ev.name, ev.ts, ev.dur) == \
            ("dispatch", "dispatch@7", t0, t1 - t0)
        assert dict(ev.args) == {"step": 7, "rows": 3}
    else:
        assert len(tr) == 0


def test_phase_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown span kind"):
        StepTracer(enabled=False).phase("admit")


# -- Chrome trace export ------------------------------------------------------

def test_chrome_trace_round_trips_with_required_keys(tmp_path):
    tr = StepTracer(capacity=32, enabled=True)
    tr.add("forward", 1.0, 1.002, name="decode@1", track="engine", step=1)
    tr.add("host_sample", 1.001, 1.0015, name="sample[0:2]",
           track="worker-0", step=1)
    tr.instant("decision", name="switch", track="engine",
               sampler_mode="host")
    path = tmp_path / "trace.json"
    n = write_chrome_trace(str(path), [("engine0", tr)])
    doc = json.loads(path.read_text())           # round-trips as JSON
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == n and n >= 3
    for e in evs:                                # viewer-required keys
        assert {"ph", "ts", "pid", "tid"} <= set(e)
    xs = [e for e in evs if e["ph"] == "X"]
    assert all("dur" in e for e in xs)
    assert {e["cat"] for e in xs} == {"forward", "host_sample"}
    # µs timestamps on the shared clock
    fwd = next(e for e in xs if e["cat"] == "forward")
    assert fwd["ts"] == pytest.approx(1.0e6) and \
        fwd["dur"] == pytest.approx(2000.0)
    insts = [e for e in evs if e["ph"] == "i"]
    assert insts and all(e["s"] == "t" for e in insts)
    # process + per-track thread metadata
    metas = [e for e in evs if e["ph"] == "M"]
    names = {(e["name"], e["args"]["name"]) for e in metas}
    assert ("process_name", "engine0") in names
    assert ("thread_name", "engine") in names
    assert ("thread_name", "worker-0") in names


def test_chrome_trace_separates_sources_by_pid():
    a, b = StepTracer(enabled=True), StepTracer(enabled=True)
    a.add("forward", 0.0, 1.0, track="t")
    b.add("commit", 0.0, 1.0, track="t")
    evs = chrome_trace_events([("A", a), ("B", b)])
    pids = {e["args"]["name"]: e["pid"] for e in evs
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert pids["A"] != pids["B"]
    for e in evs:
        if e["ph"] == "X":
            assert e["pid"] == (pids["A"] if e["cat"] == "forward"
                                else pids["B"])


# -- metrics registry / Prometheus text --------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r'(NaN|[+-]Inf|[-+0-9.e]+)$')


def _assert_prometheus_text(text: str) -> None:
    assert text.endswith("\n")
    for line in text.strip().split("\n"):
        if line.startswith("# HELP") or line.startswith("# TYPE"):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"


def test_metrics_registry_renders_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("steps_total", "steps").inc(3)
    reg.gauge("queue_depth", "queued").set(7)
    h = reg.histogram("stall_ms", "stall", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(float("nan"))         # dropped, never poisons _sum
    h.observe(50.0)
    text = reg.render()
    _assert_prometheus_text(text)
    assert "# TYPE steps_total counter" in text
    assert "steps_total 3.0" in text
    assert 'stall_ms_bucket{le="1"} 1' in text
    assert 'stall_ms_bucket{le="10"} 2' in text
    assert 'stall_ms_bucket{le="+Inf"} 3' in text
    assert "stall_ms_count 3" in text
    assert h.sum == pytest.approx(55.5)


def test_render_registries_injects_labels_and_merges_families():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("engine_steps_total", "steps").inc(2)
    b.counter("engine_steps_total", "steps").inc(5)
    text = render_registries([({"replica": "r0"}, a),
                              ({"replica": "r1"}, b)])
    _assert_prometheus_text(text)
    assert text.count("# TYPE engine_steps_total counter") == 1
    assert 'engine_steps_total{replica="r0"} 2.0' in text
    assert 'engine_steps_total{replica="r1"} 5.0' in text


def test_registry_type_conflict_fails_loudly():
    reg = MetricsRegistry()
    reg.counter("x_total", "x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("bad name")
    assert len(DEFAULT_MS_BUCKETS) == len(set(DEFAULT_MS_BUCKETS))


def test_labelled_series_get_or_create():
    reg = MetricsRegistry()
    c1 = reg.counter("reqs_total", "reqs", status="ok")
    c2 = reg.counter("reqs_total", "reqs", status="ok")
    c3 = reg.counter("reqs_total", "reqs", status="busy")
    assert c1 is c2 and c1 is not c3


# -- engine integration -------------------------------------------------------

def _host_engine(telemetry=None, stats_window=4096):
    return Engine(smoke_model(), _params(), EngineConfig(
        max_batch=4, max_seq_len=96, algorithm="reference",
        shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
        overlap=True, sampler_mode="host", samplers=2,
        stats_window=stats_window), telemetry=telemetry)


def test_engine_emits_typed_records_and_spans():
    tel = Telemetry(tracer=StepTracer(capacity=8192, enabled=True))
    eng = _host_engine(telemetry=tel)
    try:
        eng.submit(_requests(4, max_new=6))
        eng.run()
    finally:
        eng.close()
    assert eng.stats_log and \
        all(isinstance(r, StepRecord) for r in eng.stats_log)
    # queue state stamped on every record (§17 single-stream contract)
    assert all(r.queue_depth is not None for r in eng.stats_log)
    assert all(r.is_host for r in eng.stats_log)
    kinds = {e.kind for e in tel.tracer.events()}
    # the host-mode decomposition lands in the trace: prefill + pool
    # stall + commit from the engine thread, fetch/sample from workers
    assert {"prefill", "pool_stall", "commit",
            "d2h_transfer", "host_sample"} <= kinds
    # worker spans record on the pool threads' own tracks
    tracks = {e.track for e in tel.tracer.events()
              if e.kind == "host_sample"}
    assert tracks and all(t != "MainThread" for t in tracks)
    # /metrics mirrors the record stream
    text = tel.metrics.render()
    _assert_prometheus_text(text)
    assert "engine_steps_total" in text
    assert "engine_pool_stall_ms_count" in text
    assert "engine_sampler_mode_host 1.0" in text


def test_engine_device_mode_records_forward_spans():
    tel = Telemetry(tracer=StepTracer(capacity=8192, enabled=True))
    eng = Engine(smoke_model(), _params(), EngineConfig(
        max_batch=4, max_seq_len=96, algorithm="reference",
        shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
        overlap=True, sampler_mode="device"), telemetry=tel)
    try:
        eng.submit(_requests(3, max_new=5))
        eng.run()
    finally:
        eng.close()
    assert all(not r.is_host for r in eng.stats_log)
    kinds = {e.kind for e in tel.tracer.events()}
    assert "forward" in kinds and "pool_stall" not in kinds


def test_stats_log_is_bounded_by_stats_window():
    eng = _host_engine(stats_window=6)
    try:
        eng.submit(_requests(4, max_new=12))
        eng.run()
        assert len(eng.stats_log) == 6          # ring kept the tail only
        assert eng.stats_log.maxlen == 6
        steps = [r.step for r in eng.stats_log]
        assert steps == sorted(steps)
    finally:
        eng.close()


def test_default_engine_has_disabled_tracer_and_no_span_records():
    eng = _host_engine()                        # no telemetry passed
    try:
        assert not eng.tracer.enabled
        eng.submit(_requests(2, max_new=4))
        eng.run()
        assert len(eng.tracer) == 0             # zero flight-recorder cost
        assert eng.stats_log                    # records still flow
    finally:
        eng.close()


def test_pipeline_emits_stage_spans_per_stage_and_microbatch():
    tel = Telemetry(tracer=StepTracer(capacity=16384, enabled=True))
    eng = PipelineEngine(smoke_model(), _params(), PipelineConfig(
        stages=2, max_batch=4, max_seq_len=96, algorithm="reference",
        shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
        sampler_mode="host", samplers=2), telemetry=tel)
    try:
        eng.submit(_requests(4, max_new=6))
        eng.run()
    finally:
        eng.close()
    assert eng.stats_log and \
        all(isinstance(r, StepRecord) for r in eng.stats_log)
    assert all(r.bubble_frac is not None for r in eng.stats_log)
    assert isinstance(eng.cycle_log[0], CycleRecord)
    stage_evs = [e for e in tel.tracer.events() if e.kind == "stage"]
    seen = {(dict(e.args)["stage"], dict(e.args)["microbatch"])
            for e in stage_evs}
    # every (stage, microbatch) pair ran and was traced on its own track
    assert seen == {(s, m) for s in range(2) for m in range(2)}
    assert {e.track for e in stage_evs} == {"stage0", "stage1"}
    assert {e.kind for e in tel.tracer.events()} >= \
        {"stage", "host_sample", "d2h_transfer", "commit"}
    rep = eng.pipeline_report()                 # CycleRecord consumers
    assert rep["cycles"] > 0 and 0.0 <= rep["bubble_frac"] <= 1.0


def _device_engine(telemetry=None, overlap=False):
    return Engine(smoke_model(), _params(), EngineConfig(
        max_batch=4, max_seq_len=96, algorithm="reference",
        shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
        overlap=overlap, sampler_mode="device"), telemetry=telemetry)


def test_engine_step_phases_in_order_and_nesting(tmp_path):
    """One sequential step that admits and decodes, with the default
    (disabled) tracer: the profiler trace holds the step's phases in code
    order, the admission's children nested inside ``obs.prefill``."""
    eng = _device_engine()
    try:
        eng.submit(_requests(2, max_new=4))
        eng.step()                  # compile every program outside the trace
        eng.submit(_requests(1, max_new=4, base_seed=900))
        eng._pending.clear()
        evs = _profiled(tmp_path, eng.step)
    finally:
        eng.close()
    assert [e[0] for e in evs] == [
        "obs.schedule", "obs.prefill", "obs.admit_decide",
        "obs.admit_insert", "obs.admit_fetch", "obs.dispatch",
        "obs.drain", "obs.commit"]
    spans = {e[0]: e for e in evs}
    _, p0, p1, pst = spans["obs.prefill"]
    for child in ("obs.admit_decide", "obs.admit_insert", "obs.admit_fetch"):
        assert p0 <= spans[child][1] <= spans[child][2] <= p1
    assert pst["rows"] == 1 and pst["padded"] >= pst["tokens"] > 0
    # top-level phases do not overlap one another
    top = [e for e in evs if not e[0].startswith("obs.admit_")]
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    assert len(eng.tracer) == 0


def test_decode_program_scopes_forward_and_decision():
    """The decode program's compiled HLO carries the two scopes in its ops'
    ``op_name``, which is how a profiler trace's ops are split between
    the model and the decision plane."""
    eng = _device_engine()
    try:
        B = eng.ecfg.max_batch
        text = eng._decode_jit.lower(
            eng.params, eng.cache, eng.pstate, eng.last_tokens,
            eng._sp.as_params(), eng._sp.bias_array(),
            jnp.asarray(eng._nonce), jnp.asarray(eng._pos),
            jnp.asarray(0, jnp.int32), jnp.ones((B,), bool)
        ).compile().as_text()
    finally:
        eng.close()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("forward", "decision"):
        assert any(scope in p.split("/") for p in paths), scope


@pytest.mark.parametrize("make_engine", ["engine", "pipeline"])
def test_first_token_stamped_after_it_reaches_the_host(make_engine):
    tel = Telemetry(tracer=StepTracer(capacity=8192, enabled=True))
    if make_engine == "engine":
        eng = _device_engine(telemetry=tel, overlap=True)
    else:
        eng = PipelineEngine(smoke_model(), _params(), PipelineConfig(
            stages=2, max_batch=4, max_seq_len=96, algorithm="reference",
            shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
            sampler_mode="host", samplers=2), telemetry=tel)
    reqs = _requests(3, max_new=3)
    try:
        eng.submit(reqs)
        eng.run()
    finally:
        eng.close()
    evs = tel.tracer.events()
    for r in reqs:
        t = r.token_times[0]
        assert r.first_token_time == t
        # the stamp lies inside its admission's prefill phase, after that
        # admission's fetch of the first tokens ended
        pf, = [e for e in evs if e.kind == "prefill" and e.ts <= t <= e.end]
        fetch, = [e for e in evs if e.kind == "admit_fetch"
                  and pf.ts <= e.ts and e.end <= pf.end]
        assert t >= fetch.end


# -- live gateway endpoints ---------------------------------------------------

async def _get_text(host, port, path, timeout=30.0):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                 f"Connection: close\r\n\r\n".encode())
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), timeout)
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    headers = dict(
        (k.strip().lower(), v.strip())
        for k, _, v in (ln.partition(b":")
                        for ln in head.split(b"\r\n")[1:] if ln))
    return status, headers, body.decode("utf-8")


def test_gateway_metrics_and_trace_endpoints():
    fleet = ReplicaFleet(
        [_host_engine(telemetry=Telemetry(
            tracer=StepTracer(capacity=8192, enabled=True)))],
        capacity=4)
    gw = GatewayServer(fleet, trace=True)

    async def drive():
        await gw.serve(port=0)
        try:
            results = await asyncio.gather(*[
                stream_completion(gw.host, gw.port, {
                    "prompt": p, "max_tokens": 6, "seed": 7000 + i,
                }) for i, p in enumerate(PROMPTS)])
            assert all(r.status == 200 for r in results)
            m_status, m_headers, m_body = await _get_text(
                gw.host, gw.port, "/metrics")
            t_status, trace_doc = await request_json(
                gw.host, gw.port, "/v1/trace")
            return m_status, m_headers, m_body, t_status, trace_doc
        finally:
            await gw.shutdown()

    m_status, m_headers, m_body, t_status, trace_doc = asyncio.run(drive())
    assert m_status == 200
    assert m_headers[b"content-type"].startswith(b"text/plain")
    _assert_prometheus_text(m_body)
    # the wire-level decomposition the SLO argument needs...
    assert "gateway_ttft_ms_count" in m_body
    assert "gateway_tpot_ms_bucket" in m_body
    assert "gateway_queue_ms_count" in m_body
    assert 'gateway_requests_total{status="ok"} 3.0' in m_body
    assert "gateway_replica_load" in m_body
    # ...merged with the replica engine's registry under its name
    assert 'engine_steps_total{replica="replica-0"}' in m_body or \
        re.search(r'engine_steps_total\{replica="[^"]+"\}', m_body)
    assert re.search(r'engine_pool_stall_ms_count\{replica="[^"]+"\}',
                     m_body)
    assert re.search(r'engine_queue_depth\{replica="[^"]+"\}', m_body)
    # /v1/trace: a valid Chrome trace with gateway + engine spans
    assert t_status == 200
    evs = trace_doc["traceEvents"]
    assert evs and all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    cats = {e.get("cat") for e in evs if e["ph"] == "X"}
    assert "request" in cats            # the gateway's wire-level span
    assert "host_sample" in cats        # the replica's pool workers
    pnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert "gateway" in pnames and len(pnames) == 2
