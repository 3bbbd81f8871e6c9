"""Distribution correctness, run in a subprocess with 8 forced host devices
(the main test process must keep seeing ONE device).

Checks:
* sequence-parallel sampling produces bit-identical tokens to the
  single-device decision plane (the paper's determinism claim, §5.1);
* expert-parallel (shard_map) MoE matches the local dispatch numerically;
* the production mesh builders construct the right topologies.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    assert len(jax.devices()) == 8

    from repro.config import SamplingConfig, SHVSConfig
    from repro.core import penalties as pen
    from repro.core.decision_plane import DecisionPlane
    from repro.core.sampling import SamplingParams, truncation_first_sample
    from repro.models import dist

    B, V = 16, 256
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(0, 3, (B, V)).astype(np.float32))
    params = SamplingParams.broadcast(B, SamplingConfig(
        temperature=0.9, top_k=20, repetition_penalty=1.2))

    def run(mesh, mode):
        dp = DecisionPlane(V, algorithm="shvs", shvs=SHVSConfig(hot_size=32),
                           sampling_parallelism=mode, k_cap=64, seed=7)
        st = dp.init_state(B, prompt_tokens=jnp.asarray(
            rng.integers(0, V, (B, 4))))
        if mesh is None:
            toks, st2, _ = jax.jit(dp.step, static_argnames=())(
                z, st, params, jnp.asarray(0))
            return np.asarray(toks)
        with dist.use_mesh(mesh, batch_axes=("data",), model_axes=("model",)):
            zz = jax.device_put(z, NamedSharding(mesh, P("data", "model")))
            toks, st2, _ = jax.jit(dp.step)(zz, st, params, jnp.asarray(0))
            return np.asarray(toks)

    rng = np.random.default_rng(0)   # reset for identical prompt draws
    single = run(None, "sequence_parallel")
    rng = np.random.default_rng(0)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    seqp = run(mesh, "sequence_parallel")
    rng = np.random.default_rng(0)
    gath = run(mesh, "vocab_gather")
    assert (single == seqp).all(), (single, seqp)
    assert (single == gath).all(), (single, gath)
    print("SEQ_PARALLEL_DETERMINISM_OK")

    # --- expert-parallel MoE == local MoE -------------------------------
    from repro.config import get_arch
    from repro.models.moe import apply_moe, init_moe
    cfg = get_arch("granite-moe-1b-a400m").reduced()
    key = jax.random.PRNGKey(0)
    p = init_moe(key, cfg)
    x = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))
    y_local, aux_local = apply_moe(p, x, cfg, train=True)
    with dist.use_mesh(mesh, batch_axes=("data",), model_axes=("model",)):
        xx = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ep, aux_ep = jax.jit(lambda p, x: apply_moe(p, x, cfg, train=True))(p, xx)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                               rtol=2e-4, atol=2e-4)
    # aux loss: EP computes the Switch load-balance loss per data shard and
    # averages (mean of products), the local path computes it globally
    # (product of means) — same estimator family, small batch-split gap
    np.testing.assert_allclose(float(aux_local), float(aux_ep), rtol=0.1)
    print("MOE_EP_MATCHES_LOCAL_OK")

    # --- hierarchical decision plane == single-device, bit-exact ---------
    B2, V2 = 16, 500   # V not divisible by tp: exercises padding
    z2 = jnp.asarray(np.random.default_rng(3).normal(0, 3, (B2, V2)).astype(np.float32))
    prompts2 = jnp.asarray(np.random.default_rng(4).integers(0, V2, (B2, 4)))
    def s2_step(dp, z, st, params, step):
        # filtered rows: the hierarchical plane reproduces the paper's S2
        # (truncation-first) on the full vocabulary, drawn with column 1
        core = params.strip_rng()
        zp = pen.apply_penalties_rows(z, st, core.repetition_penalty,
                                      core.presence_penalty,
                                      core.frequency_penalty)
        u = dp.uniforms(step, z.shape[0])
        return truncation_first_sample(zp, core, u[:, 1],
                                       k_cap=dp.k_cap).tokens, st, None

    for kw, s2 in ((dict(temperature=1.0), False),
                   (dict(temperature=0.0), False),
                   (dict(temperature=0.9, top_k=20), True),
                   (dict(temperature=0.8, top_p=0.9), True)):
        params2 = SamplingParams.broadcast(B2, SamplingConfig(
            repetition_penalty=1.2, **kw))
        dp_ref = DecisionPlane(V2, algorithm="shvs",
                               shvs=SHVSConfig(hot_size=64),
                               sampling_parallelism="sequence_parallel",
                               k_cap=64, seed=7)
        st_ref = dp_ref.init_state(B2, prompt_tokens=prompts2)
        ref_step = (lambda *a: s2_step(dp_ref, *a)) if s2 else dp_ref.step
        t_ref, _, _ = jax.jit(ref_step)(z2, st_ref, params2, jnp.asarray(0))
        dp_h = DecisionPlane(V2, algorithm="shvs", shvs=SHVSConfig(hot_size=64),
                             sampling_parallelism="hierarchical", k_cap=64,
                             seed=7)
        st_h = dp_h.init_state(B2, prompt_tokens=prompts2)
        mesh2 = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
        with dist.use_mesh(mesh2, batch_axes=("data",), model_axes=("model",)):
            zz2 = jax.device_put(z2, NamedSharding(mesh2, P("data", "model")))
            t_h, _, _ = jax.jit(dp_h.step)(zz2, st_h, params2, jnp.asarray(0))
        assert (np.asarray(t_ref) == np.asarray(t_h)).all(), (kw, t_ref, t_h)
    print("HIERARCHICAL_EXACT_OK")

    # --- mesh builders ----------------------------------------------------
    from repro.launch.mesh import make_local_mesh
    m = make_local_mesh(2, 4)
    assert m.shape == {"data": 2, "model": 4}
    print("MESH_OK")
""")


@pytest.mark.slow
def test_distribution_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SEQ_PARALLEL_DETERMINISM_OK" in out.stdout
    assert "MOE_EP_MATCHES_LOCAL_OK" in out.stdout
    assert "HIERARCHICAL_EXACT_OK" in out.stdout
    assert "MESH_OK" in out.stdout


@pytest.mark.slow
def test_dryrun_single_combination():
    """The dry-run machinery itself (512 devices) on the cheapest combo."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "whisper-base",
         "--shape", "decode_32k", "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "dry-run complete: 1/1 ok" in out.stdout
