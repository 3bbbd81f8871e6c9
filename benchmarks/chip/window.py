"""The window rule: end-to-end metrics from what the client saw.

* ``output_tok_s``: tokens the client received inside ``[t_open, t_close]``
  over the window's seconds.
* ``ttft_p95_ms``: 95th percentile, over every request due inside the
  window, of its first token's stamp minus its due time. A request with no
  first token by the close counts at ``t_close - due``: a stall cannot hide.
* ``itl_p95_ms``: 95th percentile of every gap between consecutive tokens of
  a request whose later token lies inside the window.

Percentiles are numpy's default (linear interpolation) over all samples.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def end_to_end(sent: List, t_open: float, t_close: float) -> Dict[str, float]:
    """``sent``: ``loop.Sent`` records (``due`` and ``stamps``)."""
    tokens = 0
    ttft: List[float] = []
    itl: List[float] = []
    for s in sent:
        st = np.asarray(s.stamps)
        inside = (st >= t_open) & (st <= t_close)
        tokens += int(inside.sum())
        if t_open <= s.due <= t_close:
            first = st[0] if len(st) and st[0] <= t_close else t_close
            ttft.append(first - s.due)
        if len(st) > 1:
            itl.extend(np.diff(st)[inside[1:]].tolist())
    if not ttft or not itl:
        raise RuntimeError(
            f"the window saw {len(ttft)} due requests and {len(itl)} token "
            "gaps: nothing to measure")
    return {
        "output_tok_s": tokens / (t_close - t_open),
        "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
        "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3,
        "n_due": len(ttft),
        "n_gaps": len(itl),
        "tokens": tokens,
    }
