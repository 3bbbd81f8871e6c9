"""Pluggable sampler backends — the decision-plane service API v1.

SIMPLE's core claim is that sampling is a *service*: a decision plane
disaggregated from the data plane (§1, §4.2). This module is the narrow,
versioned contract that makes the claim concrete (DESIGN.md §11):

* :class:`SamplerBackend` — the protocol. A backend is a stateless
  logits→token draw: ``step(z, params, uniforms)`` turns penalized logits
  into ``(tokens, DecisionStats)``. Everything around the draw —
  pre-generated uniforms, penalties, S1 re-sharding, the histogram state
  and its updates, constrained-decoding masks — is owned by the service
  shell (`DecisionPlane`), so a backend is exactly one interchangeable
  sampling algorithm.
* a **registry** — backends are selected by name
  (:func:`make_backend` / :func:`registered_backends`); an unknown name is
  a `ValueError` listing what is registered, never a silent fall-through.

Built-in backends, each kept for one reason:

  ``reference``  full-V masked softmax — the oracle the tests compare with
  ``shvs``       S2 + S3 speculative hot-vocab sampling — what serves
                 (registered by ``repro.core.shvs``); rows whose filter
                 support leaves the hot set fall back to the paper's S2
                 (``truncation_first_sample``) on the full vocabulary
  ``fused``      the whole decision in ONE Pallas pass — penalties →
                 temperature → truncation-first filter → Gumbel draw
                 (``kernels/fused_kernel.py``, DESIGN.md §14) — the
                 single-pass candidate against SHVS's full-V fallback

Contract invariants (pinned by ``tests/test_service_api.py``):

* backends agree **bit-for-bit** wherever their draw rules coincide —
  greedy rows (τ=0 / ``greedy``) and single-token supports (``top_k=1``,
  collapsed nucleus) — across {overlapped, sequential} × {contiguous,
  paged} engine modes;
* elsewhere they agree **in distribution** (the TVD/exactness suites);
* every backend consumes the same pre-generated uniforms, so each
  backend's own stream obeys the engine's (seed, request, position)
  determinism contract (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import jax.numpy as jnp

from repro.core.sampling import SamplingParams, sample_reference


class DecisionStats(NamedTuple):
    """Per-step observability emitted by every backend."""

    accept_rate: jnp.ndarray     # mean fast-path acceptance
    alpha_mean: jnp.ndarray      # mean hot-vocab mass (1 when not applicable)
    fallback_rate: jnp.ndarray   # fraction of rows that took the full path


class SamplerBackend:
    """Protocol: one interchangeable sampling algorithm.

    Subclasses set ``name`` (the registry key) and implement :meth:`step`.
    Constructors are invoked by the registry with the full service
    configuration as keyword arguments — ``vocab_size``, ``k_cap``,
    ``shvs`` (an ``SHVSConfig``), ``hot_set`` — and take what
    they need (accept ``**_`` for the rest), so new backends can add
    knobs without touching the engine.
    """

    name: str = "abstract"

    #: a backend that applies Eq. 1 penalties itself, inside its own pass.
    #: The service shell then skips ``apply_penalties_rows`` and hands the
    #: backend RAW (post-bias/mask) logits plus the histogram ``state``
    #: as a ``step(..., state=...)`` keyword — the fusion seam that lets a
    #: single-pass kernel own the whole pipeline without the shell
    #: materializing a penalized (B, V) intermediate.
    fuses_penalties: bool = False

    def step(self, z: jnp.ndarray, params: SamplingParams,
             uniforms: jnp.ndarray) -> Tuple[jnp.ndarray, DecisionStats]:
        """Draw one token per row.

        ``z``: penalized (NOT temperature-scaled) logits (B, V) f32 — or,
        for ``fuses_penalties`` backends, raw logits (the shell then also
        passes ``state=`` with the penalty histograms).
        ``params``: the 7-field core controls (RNG tags already stripped).
        ``uniforms``: (B, 3) pre-generated uniforms — (accept, hot, tail)
        draws; backends that need fewer use a fixed subset so unrelated
        backends never contend for the same stream.
        Returns ``(tokens (B,) int32, DecisionStats)``.
        """
        raise NotImplementedError


_REGISTRY: Dict[str, Callable[..., SamplerBackend]] = {}


def register_backend(name: str):
    """Class decorator: register a :class:`SamplerBackend` under ``name``."""

    def deco(factory):
        _REGISTRY[name] = factory
        return factory

    return deco


def _ensure_builtin() -> None:
    # shvs registers its backend on import; import here (not at module top)
    # because shvs imports this module for the protocol.
    from repro.core import shvs  # noqa: F401


def registered_backends() -> Tuple[str, ...]:
    """Names of every registered sampler backend, sorted."""
    _ensure_builtin()
    return tuple(sorted(_REGISTRY))


def make_backend(name: str, **kwargs) -> SamplerBackend:
    """Instantiate the backend registered under ``name``.

    Raises a ``ValueError`` naming the registered backends on an unknown
    name — the decision plane calls this on every (re)configuration, so a
    typo'd algorithm fails loudly instead of falling through.
    """
    _ensure_builtin()
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown sampler backend {name!r}; registered backends: "
            f"{', '.join(sorted(_REGISTRY))}")
    return _REGISTRY[name](**kwargs)


# ---------------------------------------------------------------------------
# Built-in backends (SHVS lives in repro.core.shvs, next to its math)
# ---------------------------------------------------------------------------


@register_backend("reference")
class ReferenceBackend(SamplerBackend):
    """Full-vocabulary masked softmax — the baseline oracle (§2.1)."""

    name = "reference"

    def __init__(self, **_):
        pass

    def step(self, z, params, uniforms):
        tokens = sample_reference(z, params, uniforms[:, 1])
        stats = DecisionStats(jnp.ones(()), jnp.ones(()), jnp.zeros(()))
        return tokens, stats


@register_backend("fused")
class FusedBackend(SamplerBackend):
    """The entire decision in ONE Pallas pass (DESIGN.md §14): penalties →
    temperature → streaming top-K/masses → truncation-first filter →
    restricted Gumbel-max draw, reading the (B, V) logits exactly once
    with no (B, V) intermediate (``kernels/fused_kernel.py``).

    ``fuses_penalties`` makes the service shell hand this backend raw
    logits plus the histogram state; the kernel applies Eq. 1 in-tile with
    the same float op order as ``apply_penalties_rows``, so greedy /
    single-support rows stay bit-identical to the ``reference`` backend.
    The stochastic draw is keyed only on the row's pre-generated uniform
    and the candidate's vocab id, so tokens obey the engine's
    batch-composition and cross-mode determinism contracts.

    ``hot_set`` defaults like the ``shvs`` backend's so the fused
    pass reports the same α statistic and plugs into the
    ``HotSizeController`` autotune loop; re-resolution on hot-set swap
    re-specializes the kernel (pinned by ``tests/test_fused_backend.py``).
    """

    name = "fused"
    fuses_penalties = True

    def __init__(self, *, vocab_size: int, k_cap: int = 1024, shvs=None,
                 hot_set=None, block_b: int = 8, block_v: int = 2048, **_):
        from repro.core.shvs import default_hot_set
        self.hot_set = hot_set if hot_set is not None else \
            default_hot_set(vocab_size, shvs)
        self.k_cap = k_cap
        self.block_b = block_b
        self.block_v = block_v

    def step(self, z, params, uniforms, *, state):
        from repro.kernels import ops
        tokens, exact, alpha, kept = ops.fused_sample(
            z, state.prompt_counts, state.output_counts, params,
            uniforms[:, 1], self.hot_set.mask, k_cap=self.k_cap,
            block_b=self.block_b, block_v=self.block_v)
        stats = DecisionStats(jnp.ones(()), alpha.mean(),
                              1.0 - exact.mean())
        return tokens, stats
