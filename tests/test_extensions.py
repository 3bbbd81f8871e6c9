"""Beyond-paper extensions: online hot-size controller (paper future-work
(i)), constrained decoding. (The paged KV cache suite moved to
tests/test_paged_cache.py — DESIGN.md §9.)"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SamplingConfig, SHVSConfig
from repro.core.autotune import HotSizeController, fit_zipf_s, zipf_alpha_curve
from repro.core.decision_plane import DecisionPlane
from repro.core.sampling import SamplingParams, masked_probs_reference


class TestHotSizeController:
    def test_zipf_fit_roundtrip(self):
        V = 32768
        for s_true in (1.1, 1.4, 2.0):
            H = 2048
            alpha = zipf_alpha_curve(V, s_true, np.asarray([H]))[0]
            s_fit = fit_zipf_s(V, H, alpha)
            assert abs(s_fit - s_true) < 0.02, (s_true, s_fit)

    def test_controller_converges_to_hstar(self):
        """Feed observations from a known Zipf workload: the controller's H
        must settle near the offline sizing model's optimum."""
        V, s_true = 32768, 1.15
        ctl = HotSizeController(vocab_size=V, h_current=V // 2,
                                adjust_every=4, hysteresis=0.1)
        rng = np.random.default_rng(0)
        for step in range(200):
            alpha = zipf_alpha_curve(V, s_true, np.asarray([ctl.h_current]))[0]
            ctl.observe(alpha + rng.normal(0, 0.01))
        # offline optimum under the same constants
        from repro.core.sizing import SizingModel
        hs = np.unique(np.geomspace(256, V, 96).astype(np.int64))
        model = SizingModel(c0=ctl.c0, c=ctl.c, vocab_size=V,
                            alpha_hs=hs.astype(np.float64),
                            alpha_vals=zipf_alpha_curve(V, s_true, hs))
        h_star = model.optimal_h(lo=256)
        assert abs(np.log2(ctl.h_current / h_star)) < 0.75, \
            (ctl.h_current, h_star, ctl.history[-3:])

    def test_ewma_reset_prevents_thrash_on_h_change(self):
        """Regression (ISSUE 5): moving H must restart the observation
        window. The old code kept ``_alpha_ewma`` — measured at the OLD
        H — after the move, so the next fits chased a stale Zipf curve
        and the controller thrashed across the hysteresis band
        (497→283→366→448 on this exact deterministic trace)."""
        V = 32768
        ctl = HotSizeController(vocab_size=V, h_current=8192,
                                adjust_every=2, hysteresis=0.25, ewma=0.1)

        def drive(s_true, steps):
            changes = []
            for _ in range(steps):
                alpha = zipf_alpha_curve(V, s_true,
                                         np.asarray([ctl.h_current]))[0]
                nh = ctl.observe(alpha)
                if nh is not None:
                    changes.append(nh)
                    # the reset itself: EWMA cleared, window restarted
                    assert ctl._alpha_ewma is None
                    assert ctl._step == 0
            return changes

        # regime A: peaked workload — one decisive move, then silence
        a = drive(1.6, 120)
        assert len(a) == 1, f"thrash in a stationary regime: {a}"
        # regime B: tail flattens — H climbs monotonically, no reversals,
        # and converges in a few moves instead of stale-EWMA hunting
        b = drive(1.05, 120)
        assert b and b[-1] > a[-1]
        assert all(x < y for x, y in zip(b, b[1:])), f"oscillation: {b}"
        assert len(b) <= 3, f"stale-EWMA hunting: {b}"

    def test_domain_shift_reacts(self):
        """ᾱ collapse (domain shift, paper §9) must drive H upward."""
        V = 32768
        ctl = HotSizeController(vocab_size=V, h_current=1024,
                                adjust_every=2, hysteresis=0.05)
        for _ in range(20):
            ctl.observe(0.95)
        h_good = ctl.h_current
        for _ in range(60):
            ctl.observe(0.30)      # hot set suddenly covers little mass
        assert ctl.h_current > h_good


class TestConstrainedDecoding:
    """Allow-list / grammar-constrained decoding (paper future work (iii))."""

    def test_tokens_always_allowed(self):
        rng = np.random.default_rng(7)
        B, V = 6, 64
        z = jnp.asarray(rng.normal(0, 3, (B, V)).astype(np.float32))
        allow = jnp.asarray(rng.random((B, V)) < 0.3)
        allow = allow.at[:, 0].set(True)      # never-empty support
        # hot_size=1: SHVS's full-vocabulary S2 fallback serves the rows
        for algo, hot in (("reference", 0), ("shvs", 0), ("shvs", 1)):
            dp = DecisionPlane(V, algorithm=algo, shvs=SHVSConfig(hot_size=hot),
                               k_cap=32, seed=3)
            params = SamplingParams.broadcast(B, SamplingConfig(
                temperature=0.9, top_k=10))
            state = dp.init_state(B)
            allowed = np.asarray(allow)
            for step in range(15):
                t, state, _ = dp.step(z, state, params, step,
                                      allow_mask=allow)
                assert allowed[np.arange(B), np.asarray(t)].all(), algo

    def test_constrained_distribution_exact(self):
        rng = np.random.default_rng(8)
        B, V = 2, 48
        z = jnp.asarray(rng.normal(0, 2, (B, V)).astype(np.float32))
        allow = jnp.asarray(rng.random((B, V)) < 0.5).at[:, 0].set(True)
        params = SamplingParams.broadcast(B, SamplingConfig(temperature=1.0))
        masked = jnp.where(allow, z, -1e30)
        target = np.asarray(masked_probs_reference(masked, params))
        dp = DecisionPlane(V, algorithm="shvs", k_cap=32, seed=0)
        state = dp.init_state(B)
        stepped = jax.jit(dp.step)
        N = 4000
        toks = np.stack([np.asarray(stepped(z, state, params, s,
                                            allow_mask=allow)[0])
                         for s in range(N)])
        for b in range(B):
            emp = np.bincount(toks[:, b], minlength=V) / N
            assert 0.5 * np.abs(emp - target[b]).sum() < 0.06
