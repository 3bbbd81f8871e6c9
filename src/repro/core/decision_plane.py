"""The DecisionPlane service — SIMPLE's disaggregated sampling plane (§4.2).

Service API v1 (DESIGN.md §11): the plane is a *service shell* around a
pluggable :class:`~repro.core.sampler_backend.SamplerBackend` selected by
name from the backend registry. The shell owns everything that must be
common to all backends —

  S1  sequence-parallel re-shard            (sequence_parallel.py)
  RNG pre-generated per-request uniforms    (uniforms / uniforms_tagged)
  penalties + per-request logit bias        (penalties.py, §4)
  constrained-decoding allow masks
  histogram (Eq. 5) state updates

— while the logits→token draw itself is the backend:

  "reference" — full-V masked softmax (the oracle tests compare with)
  "shvs"      — S2 + S3 (the full SIMPLE decision plane; what serves)
  "fused"     — the whole pipeline in one Pallas pass (§14); its
                ``fuses_penalties`` flag moves the Eq. 1 penalty
                application from the shell into the kernel

The service is a separate jitted program from the model forward — the
runtime can dispatch the next microbatch's forward while sampling for the
previous one completes (the paper's "overlappable" property, realized via
async dispatch rather than a CPU sidecar; see DESIGN.md §2).

Determinism: uniforms come from counter-based keys — ``fold_in(seed, step)``
for standalone use, or ``fold_in(fold_in(seed, request), position)`` when the
engine passes ``rng_tags`` — so tokens are bit-identical for 1 sampler or 512
and invariant to scheduling/admission timing (the paper's pre-generated RNG
scheme, §5.1; DESIGN.md §2). A request carrying its own ``seed`` draws from
``fold_in(fold_in(PRNGKey(seed), tag), position)`` instead: its stream is a
pure function of (request seed, position), independent of the engine seed,
its request id, and everything else in the batch (DESIGN.md §11).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import SHVSConfig
from repro.core import penalties as pen
from repro.core.sampler_backend import (DecisionStats, SamplerBackend,
                                        make_backend, registered_backends)
from repro.core.sampling import SamplingParams
from repro.core.sequence_parallel import reshard_for_sampling, shard_decision_state
from repro.core.shvs import HotSet

# decorrelates per-request seeded streams from the engine-keyed streams
_SEED_STREAM_TAG = 0x5EEDC0DE


class DecisionPlane:
    """Stateless-per-step sampling service speaking the backend protocol.

    ``algorithm`` selects a registered backend by name (see
    ``repro.core.sampler_backend``); an unknown name raises a ``ValueError``
    listing the registered backends — at construction AND at :meth:`step`
    (the attribute is deliberately mutable: the dry-run lowers one plane per
    algorithm by reassigning it).
    """

    def __init__(self, vocab_size: int, *, algorithm: str = "shvs",
                 shvs: SHVSConfig = SHVSConfig(),
                 hot_set: Optional[HotSet] = None,
                 sampling_parallelism: str = "sequence_parallel",
                 k_cap: int = 1024, seed: int = 0):
        self.vocab_size = vocab_size
        self.algorithm = algorithm
        self.shvs_cfg = shvs
        self.hot_set = hot_set
        self.parallelism = sampling_parallelism
        self.k_cap = k_cap
        self.seed = seed
        self._backend: Optional[SamplerBackend] = None
        self._backend_key = None
        self._resolve_backend()        # fail fast on unknown algorithm names

    def _resolve_backend(self) -> SamplerBackend:
        """The backend for the current (algorithm, hot_set) configuration.

        Re-resolved lazily so post-init mutation — the dry-run reassigning
        ``algorithm``, the autotuner swapping ``hot_set`` — takes effect on
        the next step; unknown names raise the registry's ``ValueError``.
        """
        key = (self.algorithm, id(self.hot_set))
        if self._backend is None or self._backend_key != key:
            self._backend = make_backend(
                self.algorithm, vocab_size=self.vocab_size, k_cap=self.k_cap,
                shvs=self.shvs_cfg, hot_set=self.hot_set)
            self._backend_key = key
            if self.hot_set is None and hasattr(self._backend, "hot_set"):
                # surface the backend's default hot set (autotuner reads it)
                self.hot_set = self._backend.hot_set
                self._backend_key = (self.algorithm, id(self.hot_set))
        return self._backend

    # -- state ---------------------------------------------------------------
    def init_state(self, batch: int, prompt_tokens=None, prompt_lens=None
                   ) -> pen.PenaltyState:
        """Per-batch decision state: the token histograms of Eq. 5."""
        return pen.init_state(batch, self.vocab_size, prompt_tokens,
                              prompt_lens)

    def uniforms(self, step, batch: int):
        """Deterministic (B, 3) uniforms for (accept, hot, tail) draws."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 jnp.asarray(step, jnp.uint32))
        return jax.random.uniform(key, (batch, 3), jnp.float32)

    def uniforms_tagged(self, nonces, positions, seeds=None, use_seed=None):
        """Per-request (B, 3) uniforms: row b draws from
        ``fold_in(fold_in(seed, nonce_b), pos_b)`` (the paper's pre-generated
        RNG, §5.1/DESIGN.md §2). Tying the counter to (request, position)
        instead of the global iteration makes tokens independent of
        *scheduling*: a request samples the same stream whether it was
        admitted one step earlier or later, on any slot, in overlapped or
        sequential engine mode.

        ``seeds`` / ``use_seed`` (both (B,), optional): rows with
        ``use_seed`` draw from ``fold_in(fold_in(PRNGKey(seeds_b), tag),
        pos_b)`` instead — the per-request seeding contract (DESIGN.md §11):
        the stream is a pure function of (request seed, position),
        independent of the engine seed and the request id. Rows without it
        keep the engine-keyed stream bit-for-bit.
        """
        base = jax.random.PRNGKey(self.seed)
        if seeds is None or use_seed is None:
            def row(n, p):
                k = jax.random.fold_in(jax.random.fold_in(base, n), p)
                return jax.random.uniform(k, (3,), jnp.float32)

            return jax.vmap(row)(jnp.asarray(nonces, jnp.uint32),
                                 jnp.asarray(positions, jnp.uint32))

        def row(n, p, s, g):
            k_eng = jax.random.fold_in(jax.random.fold_in(base, n), p)
            k_req = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(s), jnp.uint32(_SEED_STREAM_TAG)), p)
            return jax.random.uniform(jnp.where(g, k_req, k_eng), (3,),
                                      jnp.float32)

        return jax.vmap(row)(jnp.asarray(nonces, jnp.uint32),
                             jnp.asarray(positions, jnp.uint32),
                             jnp.asarray(seeds, jnp.uint32),
                             jnp.asarray(use_seed, bool))

    # -- the per-iteration decision ------------------------------------------
    def step(self, logits, state: pen.PenaltyState, params: SamplingParams,
             step_idx, active=None, allow_mask=None, rng_tags=None,
             logit_bias=None):
        """logits: (B, V) from the LM head. Returns (tokens, state, stats).

        ``allow_mask``: optional (B, V) bool — grammar/allow-list constrained
        decoding (the paper's future work (iii)): disallowed tokens are
        masked to −inf BEFORE the filter pipeline, so truncation-first /
        SHVS exactness machinery applies unchanged (the mask simply composes
        into Filter(·), §5.2).

        ``rng_tags``: optional ``(nonces (B,), positions (B,))`` — draw
        per-request uniforms (see :meth:`uniforms_tagged`) instead of the
        per-iteration stream keyed on ``step_idx``. The serving engine passes
        (request-id, output-position) so sampled tokens are invariant to
        admission timing and slot placement (DESIGN.md §2). Rows whose
        ``params`` carry ``seed``/``use_seed`` draw their own seeded stream
        instead (DESIGN.md §11).

        ``logit_bias``: optional (B, V) f32 added to the logits before
        penalties and filtering (the per-request ``SamplingConfig.logit_bias``
        contract; the engine materializes the dense rows).
        """
        B = logits.shape[0]
        backend = self._resolve_backend()   # ValueError on unknown algorithm
        if logit_bias is not None:
            logits = logits + logit_bias
        if allow_mask is not None:
            logits = jnp.where(allow_mask, logits, -1e30)

        def draw_uniforms():
            if rng_tags is not None:
                return self.uniforms_tagged(*rng_tags, seeds=params.seed,
                                            use_seed=params.use_seed)
            return self.uniforms(step_idx, B)

        core = params.strip_rng()   # backends speak the 7-field core struct
        from repro.models import dist as _dist
        if self.parallelism == "hierarchical" and _dist.get_ctx().active:
            # beyond-paper: decide in place on (B@batch, V@model) shards
            from repro.core.hierarchical import hierarchical_sample
            u = draw_uniforms()
            tokens, state, res = hierarchical_sample(
                logits, state, core, u, self.hot_set, k_cap=self.k_cap)
            if active is not None:
                tokens = jnp.where(active, tokens, 0)
            stats = DecisionStats(res.accepted.mean(), res.alpha.mean(),
                                  (~res.exact_fast).mean())
            return tokens, state, stats
        # S1: re-shard the decision plane along the batch axis
        logits = reshard_for_sampling(logits, self.parallelism)
        state = shard_decision_state(state, self.parallelism)
        u = draw_uniforms()
        u = shard_decision_state(u, self.parallelism)

        if backend.fuses_penalties:
            # the backend applies Eq. 1 inside its own single pass: hand it
            # raw (post-bias/mask) logits + the histogram state, and never
            # materialize a penalized (B, V) intermediate
            tokens, stats = backend.step(logits, core, u, state=state)
        else:
            z = pen.apply_penalties_rows(logits, state,
                                         core.repetition_penalty,
                                         core.presence_penalty,
                                         core.frequency_penalty)
            tokens, stats = backend.step(z, core, u)
        state = pen.update_histograms(state, tokens, active)
        return tokens, state, stats


__all__ = ["DecisionPlane", "DecisionStats", "registered_backends"]
