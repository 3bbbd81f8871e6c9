"""``chip_smoke.py``'s phases at CPU size, and the entry points' compile
cache.

The script itself refuses to run off a TPU; here its phase functions run
on a ``.reduced()`` model with the TPU-only check (``tpu_custom_call`` in
the fused decode program) skipped. Each phase raises on a failed check.
"""
import importlib.util
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


cs = _load_script()


@pytest.fixture(scope="module")
def smoke():
    """The device phase runs once; its streams are the others' oracle."""
    ctx = cs.Smoke(cs.Sizes.cpu(), on_tpu=False)
    cs.phase_device(ctx)
    return ctx


def test_device_phase_streams_every_request(smoke):
    ctx = smoke
    assert sorted(ctx.device_streams) == list(range(ctx.sizes.requests))
    assert all(len(t) == ctx.sizes.max_new
               for t in ctx.device_streams.values())
    assert any(f.startswith("[device] ") for f in ctx.facts)


def test_host_phase_samples_on_cpu(smoke):
    ctx = smoke
    cs.phase_host(ctx)
    assert any("pool_device=cpu state_device=cpu" in f for f in ctx.facts)


def test_prefill_phase_gaps_close_in_float32(smoke):
    ctx = smoke
    cs.phase_prefill(ctx)
    facts = [f for f in ctx.facts if f.startswith("[prefill] dtype=")]
    assert len(facts) == 2 and "dtype=float32" in facts[1]
    # every program picks the same top token for every prompt
    n = ctx.sizes.requests
    assert all(f"chunked_top1_equal={n}/{n}" in f and
               f"solo_top1_equal={n}/{n}" in f for f in facts)


def test_paged_chunked_phase_matches_greedy(smoke):
    cs.phase_paged(smoke)


def test_fused_phase_matches_greedy(smoke):
    ctx = smoke
    cs.phase_fused(ctx)
    # interpreted on the CPU: the kernel is not a custom call here
    assert "[fused] tpu_custom_call=False" in ctx.facts
    n = 4 * ctx.sizes.requests
    assert any(f.startswith("[fused] vs=oracle") and
               f"tokens_equal={n}/{n}" in f for f in ctx.facts)


def test_gateway_phase_wire_equals_in_process(smoke):
    ctx = smoke
    cs.phase_gateway(ctx)
    assert any(f.startswith("[gateway] ") and "streams_equal=3/3" in f
               for f in ctx.facts)


def test_script_refuses_without_a_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert "{" not in out.out          # no result line
    assert "not a TPU" in out.err


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_wins_and_nothing_else_is_set(self, monkeypatch,
                                                  tmp_path):
        from repro.launch.compile_cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_dir_in_the_checkout(self, monkeypatch):
        from repro.launch.compile_cache import (CHECKOUT,
                                                enable_compile_cache)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache") == \
            jax.config.jax_compilation_cache_dir
        assert CHECKOUT == ROOT
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
