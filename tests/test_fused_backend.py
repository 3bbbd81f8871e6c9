"""Fused-backend service seam (DESIGN.md §14): hot-set re-specialization.

The fused kernel bakes the hot-vocab mask into its traced operands, so the
SHVS autotuner's ``hot_set`` swap is a stale-operand hazard — the shape of
an earlier re-jit race, now at the backend layer. These tests pin that a
plane swapped INTO a hot set is bit-identical to a plane BUILT with it
(the ``(algorithm, id(hot_set))`` re-resolve key actually fires). The
host pool's side of the swap is ``tests/test_decision_backends.py``'s.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SamplingConfig, SHVSConfig
from repro.core import penalties as pen
from repro.core.decision_plane import DecisionPlane
from repro.core.hot_vocab import build_hot_set
from repro.core.sampling import SamplingParams
from repro.core.shvs import make_hot_set

pytestmark = pytest.mark.kernels

V = 512


def _plane(algorithm="fused", hot_set=None):
    return DecisionPlane(V, algorithm=algorithm, shvs=SHVSConfig(hot_size=64),
                         hot_set=hot_set, k_cap=64, seed=0)


def _pool_inputs(B=8, seed=0, top_k=16):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0, 2, (B, V)).astype(np.float32))
    state = pen.PenaltyState(
        prompt_counts=jnp.asarray(rng.integers(0, 2, (B, V)), jnp.int32),
        output_counts=jnp.zeros((B, V), jnp.int32))
    params = SamplingParams.broadcast(B, SamplingConfig(
        temperature=0.9, top_k=top_k, repetition_penalty=1.2))
    return (logits, state, params, None, np.arange(B, dtype=np.uint32),
            np.zeros((B,), np.int32), 0, np.ones((B,), bool))


def _swapped_hot_set(h=128, seed=7):
    """A frequency-ranked hot set unlike the default prefix [0, H)."""
    rng = np.random.default_rng(seed)
    return build_hot_set(rng.random(V), h, V)


class TestHotSwapRespecialization:
    def test_plane_step_uses_swapped_hot_set(self):
        """After ``plane.hot_set = <new>`` (the autotuner's move), the next
        step must re-specialize the fused backend on the new mask — stats
        and tokens bit-identical to a plane constructed with that hot set,
        never the stale trace's."""
        hot2 = _swapped_hot_set()
        swapped = _plane()                       # default hot set first ...
        fresh = _plane(hot_set=hot2)             # ... vs born on hot2
        logits, state, params, *_ = _pool_inputs()
        core = params.strip_rng()

        t_before, _, s_before = swapped.step(logits, state, core, 0)
        swapped.hot_set = hot2                   # the autotune swap
        t_after, _, s_after = swapped.step(logits, state, core, 0)
        t_want, _, s_want = fresh.step(logits, state, core, 0)

        np.testing.assert_array_equal(np.asarray(t_after),
                                      np.asarray(t_want))
        assert float(s_after.alpha_mean) == float(s_want.alpha_mean)
        # and the swap actually changed the operand (guards a vacuous pass:
        # the default prefix hot set must measure a different hot mass)
        assert float(s_before.alpha_mean) != float(s_after.alpha_mean)

    def test_swap_back_and_forth_tracks_current_mask(self):
        """Two swaps: the re-resolve key is (algorithm, id(hot_set)), so a
        return to an equal-but-distinct hot set must still re-specialize
        and reproduce the original stream exactly."""
        plane = _plane()
        logits, state, params, *_ = _pool_inputs(seed=3)
        core = params.strip_rng()
        t0, _, s0 = plane.step(logits, state, core, 0)
        plane.hot_set = _swapped_hot_set()
        plane.step(logits, state, core, 0)
        # equal contents, different object identity
        plane.hot_set = make_hot_set(jnp.arange(64, dtype=jnp.int32), V)
        t2, _, s2 = plane.step(logits, state, core, 0)
        np.testing.assert_array_equal(np.asarray(t0), np.asarray(t2))
        assert float(s0.alpha_mean) == float(s2.alpha_mean)
