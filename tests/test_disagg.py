"""Prefill/decode disaggregation tests (DESIGN.md §18): KV payload
export/import, migration identity, the handoff scheduler, the
disaggregated router policy, and wire identity over a live split fleet.

Marked ``disagg`` and excluded from tier-1 (they boot real engines and
sockets); CI runs them in their own step.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.config import SamplingConfig, SHVSConfig
from repro.engine import (Engine, EngineConfig, HandoffScheduler,
                          KVPayload, PipelineConfig, PipelineEngine,
                          Request)
from repro.gateway import ReplicaFleet, Router
from repro.gateway.smoke import (PROMPTS, VOCAB, reference_streams,
                                 smoke_model, wire_streams)

pytestmark = pytest.mark.disagg

_CACHE: dict = {}


def _params():
    if "params" not in _CACHE:
        from repro.models.model import Model
        _CACHE["params"] = Model(smoke_model()).init(jax.random.PRNGKey(0))
    return _CACHE["params"]


def _engine(cache="paged", overlap=True):
    return Engine(smoke_model(), _params(), EngineConfig(
        max_batch=4, max_seq_len=96, algorithm="reference",
        shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
        overlap=overlap, sampler_mode="device", cache=cache,
        block_size=16))


def _requests(seeded=True, n=3, max_new=12):
    samp = (SamplingConfig(temperature=0.9, top_k=40, seed=123) if seeded
            else SamplingConfig(greedy=True))
    return [Request(request_id=10 + i, prompt=[7 + i, 8, 9, 3 * i + 1],
                    max_new_tokens=max_new, sampling=samp)
            for i in range(n)]


def _run_single(cache, overlap, seeded):
    eng = _engine(cache, overlap)
    try:
        rs = _requests(seeded)
        out = {r.request_id: [] for r in rs}
        for ev in eng.generate(rs):
            if ev.token is not None:
                out[ev.request_id].append(ev.token)
        return out
    finally:
        eng.close()


def _run_migrated(cache_a, cache_b, overlap, seeded, via_bytes=False):
    """Prefill + a few decode steps on engine A, export every request at
    the flush boundary, import into engine B, decode to completion."""
    a, b = _engine(cache_a, overlap), _engine(cache_b, overlap)
    try:
        rs = _requests(seeded)
        a.submit(rs)
        for _ in range(50):
            a.step()
            if all(len(r.output) >= 2 for r in rs):
                break
        a.flush()
        landed = []
        for r in rs:
            p = a.export_request(r.request_id)
            if via_bytes:
                # serialization detaches the live Request: the importer
                # re-materializes one from the payload alone
                p = KVPayload.from_bytes(p.to_bytes())
            landed.append(b.import_request(p))
        for _ in range(200):
            if not (b.scheduler.has_work or b.in_flight):
                break
            b.step()
        b.flush()
        for r in landed:
            assert r.should_stop(), r
        return {r.request_id: list(r.output) for r in landed}
    finally:
        a.close()
        b.close()


# -- migration identity ------------------------------------------------------

@pytest.mark.parametrize("seeded", (True, False),
                         ids=("seeded", "greedy"))
@pytest.mark.parametrize("overlap", (True, False), ids=("overlap", "seq"))
def test_migration_identity_paged_to_paged(overlap, seeded):
    """The acceptance gate: a request that prefills on one engine and
    decodes on another produces the bit-identical stream to one that
    never moved — under both iteration loops, seeded and greedy."""
    ref = _run_single("paged", overlap, seeded)
    assert ref == _run_single("contiguous", overlap, seeded)
    assert _run_migrated("paged", "paged", overlap, seeded) == ref


@pytest.mark.parametrize("cache_a,cache_b",
                         [("paged", "contiguous"), ("contiguous", "paged")])
def test_migration_identity_cross_layout(cache_a, cache_b):
    """KVPayload is layout-invariant: paged → contiguous and
    contiguous → paged migrations are invisible in the tokens."""
    ref = _run_single("paged", True, True)
    assert _run_migrated(cache_a, cache_b, True, True) == ref


def test_migration_identity_via_serialized_payload():
    """The wire form carries everything: a migration through
    to_bytes()/from_bytes() — live Request object discarded — still
    resumes bit-identically."""
    ref = _run_single("paged", True, True)
    got = _run_migrated("paged", "paged", True, True, via_bytes=True)
    assert got == ref


def test_handoff_scheduler_identity():
    """The in-process two-engine scheduler migrates every request at its
    first committed token and the streams stay bit-identical."""
    ref = _run_single("paged", True, True)
    a, b = _engine("paged"), _engine("paged")
    hs = HandoffScheduler(a, b)
    try:
        rs = _requests(True)
        out = {r.request_id: [] for r in rs}
        for ev in hs.generate(rs):
            if ev.token is not None:
                out[ev.request_id].append(ev.token)
        assert hs.migrated > 0
        assert all(r.handoff_count == 1 for r in rs)
        assert out == ref
    finally:
        hs.close()


# -- payload format ----------------------------------------------------------

def test_payload_bytes_roundtrip():
    a = _engine("paged")
    try:
        rs = _requests(True, n=1)
        a.submit(rs)
        for _ in range(50):
            a.step()
            if rs[0].output:
                break
        a.flush()
        p = a.export_request(rs[0].request_id)
        blob = p.to_bytes()
        assert isinstance(blob, bytes) and len(blob) > 0
        assert p.nbytes > 0
        q = KVPayload.from_bytes(blob)
        np.testing.assert_array_equal(q.k, p.k)
        np.testing.assert_array_equal(q.v, p.v)
        np.testing.assert_array_equal(q.prompt_counts, p.prompt_counts)
        np.testing.assert_array_equal(q.output_counts, p.output_counts)
        assert q.k.dtype == p.k.dtype
        assert (q.request_id, q.prompt, q.output, q.kv_len, q.last_token,
                q.next_pos) == (p.request_id, p.prompt, p.output, p.kv_len,
                                p.last_token, p.next_pos)
        assert q.sampling == p.sampling
        assert q.request is None       # bytes never carry the live object
    finally:
        a.close()


def test_payload_bf16_roundtrip_is_bitwise():
    """bf16 KV widens to f32 for the wire (exact) and narrows back on
    load — the migrated cache is bitwise what was exported."""
    import ml_dtypes
    rng = np.random.default_rng(0)
    k = rng.normal(0, 3, (2, 5, 2, 8)).astype(ml_dtypes.bfloat16)
    v = rng.normal(0, 3, (2, 5, 2, 8)).astype(ml_dtypes.bfloat16)
    p = KVPayload(request_id=1, prompt=[1, 2, 3], output=[4, 5],
                  max_new_tokens=8, sampling=SamplingConfig(seed=9),
                  eos_token=None, prompt_offset=0, arrival_time=0.0,
                  kv_len=5, k=k, v=v,
                  prompt_counts=np.zeros(16, np.int32),
                  output_counts=np.zeros(16, np.int32),
                  last_token=5, next_pos=2)
    q = KVPayload.from_bytes(p.to_bytes())
    assert q.k.dtype == k.dtype and q.v.dtype == v.dtype
    assert np.array_equal(q.k.view(np.uint16), k.view(np.uint16))
    assert np.array_equal(q.v.view(np.uint16), v.view(np.uint16))


# -- error surface -----------------------------------------------------------

def test_export_unknown_or_finished_request_raises():
    eng = _engine("paged")
    try:
        with pytest.raises(KeyError):
            eng.export_request(424242)
        rs = _requests(True, n=1, max_new=2)
        for _ in eng.generate(rs):
            pass
        assert rs[0].should_stop()
        # a finished request has left its slot — nothing to export
        with pytest.raises(KeyError):
            eng.export_request(rs[0].request_id)
    finally:
        eng.close()


def test_import_rejects_malformed_payloads():
    a, b = _engine("paged"), _engine("paged", overlap=False)
    try:
        rs = _requests(True, n=1)
        a.submit(rs)
        for _ in range(50):
            a.step()
            if rs[0].output:
                break
        a.flush()
        p = a.export_request(rs[0].request_id)
        import dataclasses
        bad_shape = dataclasses.replace(p, k=p.k[:, :-1])
        with pytest.raises(ValueError):
            b.import_request(bad_shape)
        too_long = dataclasses.replace(
            p, kv_len=1000, k=np.zeros((p.k.shape[0], 1000) + p.k.shape[2:],
                                       p.k.dtype),
            v=np.zeros((p.v.shape[0], 1000) + p.v.shape[2:], p.v.dtype))
        with pytest.raises(ValueError):
            b.import_request(too_long)
        desynced = dataclasses.replace(p, next_pos=p.next_pos + 3)
        with pytest.raises(ValueError):
            b.import_request(desynced)
    finally:
        a.close()
        b.close()


def test_pipeline_engine_refuses_migrations():
    eng = PipelineEngine(smoke_model(), _params(), PipelineConfig(
        stages=2, max_batch=4, max_seq_len=96, algorithm="reference",
        shvs=SHVSConfig(hot_size=VOCAB // 4), k_cap=256,
        sampler_mode="host", samplers=2))
    try:
        r = _requests(True, n=1)[0]
        r.kv_payload = object()
        with pytest.raises(ValueError, match="single-stage"):
            eng.submit([r])
    finally:
        eng.close()


def test_migration_stats_counters():
    a, b = _engine("paged"), _engine("paged")
    try:
        free0 = a.migration_stats()["free_blocks"]
        rs = _requests(True, n=2)
        a.submit(rs)
        for _ in range(50):
            a.step()
            if all(r.output for r in rs):
                break
        a.flush()
        for r in rs:
            b.import_request(a.export_request(r.request_id))
        sa, sb = a.migration_stats(), b.migration_stats()
        assert sa["migrations_out"] == 2 and sa["migrations_in"] == 0
        # the exporter's pool is whole again: export released every block
        assert sa["free_blocks"] == free0
        # imports are queued, not yet installed (install rides admission)
        assert sb["pending_imports"] == 2 and sb["migrations_in"] == 0
        for _ in range(200):
            if not (b.scheduler.has_work or b.in_flight):
                break
            b.step()
        b.flush()
        sb = b.migration_stats()
        assert sb["migrations_in"] == 2 and sb["migrations_out"] == 0
        assert sb["pending_imports"] == 0
        assert sb["free_blocks"] == free0
    finally:
        a.close()
        b.close()


def test_contiguous_engine_reports_no_block_pool():
    eng = _engine("contiguous")
    try:
        assert eng.migration_stats()["free_blocks"] is None
    finally:
        eng.close()


# -- disaggregated router policy (fake replicas: pure policy) ----------------

class FakeReplica:
    def __init__(self, name, capacity=2, load=0):
        self.name = name
        self.capacity = capacity
        self.load = load
        self.admitted = []
        self.handoff = None

    def try_submit(self, request, sink, on_done=None, session_id=None):
        if self.load >= self.capacity:
            return False
        self.load += 1
        self.admitted.append(request)
        return True

    def reserve(self):
        if self.load >= self.capacity:
            return False
        self.load += 1
        return True

    def unreserve(self):
        self.load -= 1

    def set_handoff(self, hook):
        self.handoff = hook


def test_place_decode_least_loaded_and_pins_session():
    pre = [FakeReplica("p0", capacity=9)]
    dec = [FakeReplica("d0", capacity=9, load=3),
           FakeReplica("d1", capacity=9, load=1)]
    router = Router(pre, decode_replicas=dec)
    assert router.place_decode("sess") is dec[1]
    # the session is now pinned: even with d0 emptier, it stays on d1
    dec[0].load = 0
    assert router.place_decode("sess") is dec[1]
    # a sessionless migration goes least-loaded
    assert router.place_decode(None) is dec[0]


def test_place_decode_strict_affinity_refuses_when_sticky_full():
    pre = [FakeReplica("p0", capacity=9)]
    dec = [FakeReplica("d0", capacity=9), FakeReplica("d1", capacity=1)]
    router = Router(pre, decode_replicas=dec)
    dec[0].load = 5
    assert router.place_decode("s1") is dec[1]     # pinned to d1
    dec[0].load = 0
    dec[1].load = dec[1].capacity                  # sticky target full
    assert router.place_decode("s1") is None       # refuse, never re-home
    assert not dec[0].admitted


def test_place_decode_none_without_decode_pool_or_while_draining():
    colo = Router([FakeReplica("a")])
    assert colo.place_decode("s") is None
    dis = Router([FakeReplica("p")],
                 decode_replicas=[FakeReplica("d", capacity=9)])
    dis.stop_accepting()
    assert dis.place_decode("s") is None


def test_disaggregated_admission_skips_sticky_and_targets_prefill():
    """Admission under disaggregation is least-loaded over the PREFILL
    pool even for session-carrying requests — affinity binds at the
    decode handoff, not at admission (prefill holds no session state)."""
    pre = [FakeReplica("p0", capacity=9, load=2),
           FakeReplica("p1", capacity=9, load=0)]
    dec = [FakeReplica("d0", capacity=9)]
    router = Router(pre, decode_replicas=dec)
    assert router.place_decode("s1") is dec[0]     # pin the session
    res = router.submit("req", None, session_id="s1")
    assert res.status == "ok" and res.replica is pre[1]
    assert not dec[0].admitted                     # never admits to decode


def test_router_for_fleet_installs_handoff_hooks():
    class FakeFleet:
        def __init__(self, pre, dec):
            self.prefill_replicas = pre
            self.decode_replicas = dec

    pre = [FakeReplica("p0"), FakeReplica("p1")]
    dec = [FakeReplica("d0")]
    router = Router.for_fleet(FakeFleet(pre, dec))
    assert all(r.handoff == router.place_decode for r in pre)
    colo = Router.for_fleet(FakeFleet([FakeReplica("a")], []))
    assert colo.decode_replicas is None


class _FakeEngine:
    def generate(self, requests):
        return iter(())

    def close(self):
        pass


def test_fleet_role_validation():
    """A split fleet must have both sides: all-prefill or all-decode
    configurations are rejected at construction."""
    with pytest.raises(AssertionError):
        ReplicaFleet([_FakeEngine(), _FakeEngine()],
                     roles=["prefill", "prefill"])
    with pytest.raises(AssertionError):
        ReplicaFleet([_FakeEngine()], roles=["decode"])
    fleet = ReplicaFleet([_FakeEngine(), _FakeEngine(), _FakeEngine()],
                         roles=["prefill", "decode", "decode"])
    assert fleet.disaggregated
    assert [r.name for r in fleet.prefill_replicas] == ["replica0"]
    assert [r.name for r in fleet.decode_replicas] == ["replica1",
                                                       "replica2"]
    colo = ReplicaFleet([_FakeEngine()])
    assert not colo.disaggregated
    assert colo.prefill_replicas == colo.replicas
    assert colo.decode_replicas == []


# -- end-to-end over a live split fleet --------------------------------------

def test_disagg_wire_identity_over_http():
    """The §18 acceptance gate at the wire: seeded streams over a live
    1-prefill + 1-decode paged fleet — every request migrating at first
    token — bit-identical to in-process generation on a colocated
    contiguous engine."""
    ref = reference_streams(max_new=8)
    wire = asyncio.run(wire_streams(replicas=2, max_new=8,
                                    disaggregate=True))
    for p in PROMPTS:
        assert wire[p] == ref[p], f"stream for {p!r} diverged"


def test_migration_identity_with_donated_buffers(monkeypatch):
    """Export and import read the live cache and histograms after the
    step programs donated their inputs (the accelerator branch, steered
    on here): the migrated streams stay the unmigrated ones."""
    from repro.engine import engine as engine_mod
    ref = _run_single("paged", True, True)
    monkeypatch.setattr(engine_mod, "_donates", lambda: True)
    assert _run_single("paged", True, True) == ref
    assert _run_migrated("paged", "contiguous", True, True) == ref
