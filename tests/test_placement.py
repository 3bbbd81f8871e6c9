"""An engine on one device, its host sampler pool on another.

On an accelerator machine the engine's cache, weights and decode programs
live on the chip while host mode's pool, its penalty histograms and its
jitted step live on ``jax.devices("cpu")[0]``; a jitted program handed
arrays committed to two devices raises. The main test process sees one
CPU device, where both are the same, so each case runs in a subprocess
with two forced host devices: the engine on device 1, the pool on device
0, the step programs donating ``cache``/``pstate`` as on an accelerator.
Every case must give the streams of a plain device-mode engine on device
0, and place its arrays where host mode says.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from repro.config import ModelConfig, SamplingConfig, SHVSConfig
    from repro.engine import Engine, EngineConfig, Request
    from repro.engine import engine as engine_mod
    from repro.models.model import Model

    case = sys.argv[1]
    pool_dev, eng_dev = jax.devices()
    assert jax.devices("cpu")[0] == pool_dev
    engine_mod._donates = lambda: True
    cfg = ModelConfig(name="placement-tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=512)
    params = Model(cfg).init(jax.random.PRNGKey(0))
    chunk = 8 if case == "host-chunked" else 0
    KW = dict(max_batch=3, max_seq_len=64, algorithm="shvs",
              shvs=SHVSConfig(hot_size=64), k_cap=64, prompt_bucket=8,
              prompt_chunk=chunk)

    def reqs():
        rng = np.random.default_rng(3)
        return [Request(
            request_id=i,
            prompt=rng.integers(1, 512, int(rng.integers(3, 20))).tolist(),
            max_new_tokens=8,
            sampling=SamplingConfig(
                temperature=0.9, top_k=30, top_p=0.95,
                repetition_penalty=1.1, presence_penalty=0.2,
                seed=100 + i, logit_bias={7: -50.0} if i % 2 else {}))
            for i in range(6)]

    def engine(device, **kw):
        p = params if device is None else jax.device_put(params, device)
        return Engine(cfg, p, EngineConfig(**{**KW, **kw}))

    def finish(eng, rs):
        for _ in range(4000):
            if not (eng.scheduler.has_work or eng.in_flight):
                break
            eng.step()
        eng.flush()
        return {r.request_id: list(r.output) for r in rs}

    def placed(eng):
        assert eng.device == eng_dev
        assert eng.cache["k"].devices() == {eng_dev}
        want = pool_dev if eng.client.is_host else eng_dev
        assert eng.pstate.output_counts.devices() == {want}, \\
            (eng.client.mode, eng.pstate.output_counts.devices())

    ref_eng = engine(None)
    rs = reqs()
    ref_eng.submit(rs)
    ref = finish(ref_eng, rs)
    ref_eng.close()
    assert ref_eng.device == pool_dev

    if case == "host-chunked":
        eng = engine(eng_dev, sampler_mode="host")
        rs = reqs()
        eng.submit(rs)
        got = finish(eng, rs)
        placed(eng)
    elif case == "adaptive":
        eng = engine(eng_dev, sampler_mode="adaptive")
        eng._dpc.adjust_every = 2
        eng._dpc.dwell = 2
        eng._dpc.queue_high = -1.0      # device -> host at once, and back
        eng._dpc.queue_low = 99.0
        rs = reqs()
        eng.submit(rs)
        modes = set()
        for _ in range(4000):
            if not (eng.scheduler.has_work or eng.in_flight):
                break
            eng.step()
            placed(eng)
            modes.add(eng.client.mode)
        got = finish(eng, rs)
        assert modes == {"host", "device"}, modes
    elif case == "host-import":
        src = engine(None)
        rs = reqs()
        src.submit(rs)
        for _ in range(200):
            src.step()
            if all(len(r.output) >= 2 for r in rs):
                break
        src.flush()
        payloads = [src.export_request(r.request_id) for r in rs
                    if not r.should_stop()]
        assert payloads
        eng = engine(eng_dev, sampler_mode="host")
        landed = [eng.import_request(p) for p in payloads]
        got = finish(eng, rs)
        placed(eng)
        assert all(r.should_stop() for r in landed)
        src.close()
    eng.close()
    assert got == ref, (got, ref)
    print("PLACEMENT_OK", case)
""")


@pytest.mark.parametrize("case", ["host-chunked", "adaptive", "host-import"])
def test_engine_and_pool_on_two_devices(case):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCRIPT, case], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert f"PLACEMENT_OK {case}" in out.stdout
