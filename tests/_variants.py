"""The sampler-backend variants the differential suites run, and the
``REPRO_BACKEND`` CI matrix knob that narrows them to one backend.

A variant is a registered backend name, or ``shvs-fallback``: the ``shvs``
backend on a one-token hot set, so that no sampled row's filter support
fits the hot set and every such row is served by SHVS's full-vocabulary
truncation-first fallback (the paper's S2). It is not a registered name,
so it runs under the ``shvs`` CI leg.
"""
import os

from repro.config import SHVSConfig
from repro.core.sampler_backend import registered_backends

SHVS_FALLBACK = "shvs-fallback"


def backends_under_test():
    """All registered backends, or just $REPRO_BACKEND (the CI matrix)."""
    env = os.environ.get("REPRO_BACKEND")
    if env:
        assert env in registered_backends(), \
            f"REPRO_BACKEND={env!r} is not a registered backend"
        return (env,)
    return registered_backends()


def variants_under_test():
    """:func:`backends_under_test`, plus ``shvs-fallback`` where ``shvs``
    runs."""
    names = backends_under_test()
    return names + ((SHVS_FALLBACK,) if "shvs" in names else ())


def assert_fallback(variant: str, rate: float, served: bool) -> None:
    """For ``shvs-fallback``, check a step's ``fallback_rate``: every row
    was served by the S2 fallback (``served``: the rows are filtered and
    sampled), or none was (greedy or unfiltered rows, which the fallback
    never serves). Other variants pass unchecked."""
    if variant == SHVS_FALLBACK:
        assert rate == (1.0 if served else 0.0), rate


def variant_config(variant: str, hot_size: int):
    """``(algorithm, SHVSConfig)`` for a variant; ``hot_size`` is the
    suite's own hot-set size, which ``shvs-fallback`` overrides."""
    if variant == SHVS_FALLBACK:
        return "shvs", SHVSConfig(hot_size=1)
    return variant, SHVSConfig(hot_size=hot_size)
