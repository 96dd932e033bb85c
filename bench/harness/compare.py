"""The comparison that decides ``correct``.

Every classification the window fetched to the host is looked up in the
plain reference by its (library item, position) key.  The number compared
is ``logit_err``: the largest absolute gap between a served logit and the
reference's, over all of them, in units of the typical size of a logit
(the median over the compared classifications of their largest absolute
reference logit).  A gap that is not finite counts as infinite.
``unmatched`` counts classifications that came due in the window whose
logits never reached the host, plus logits that came for no input.  The
limits sit in the configuration file.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def logit_gaps(keys: np.ndarray, logits: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per classification: the largest |served - reference| logit gap,
    in units of the median largest |reference logit|."""
    r = ref[keys[:, 0], keys[:, 1]]
    unit = float(np.median(np.abs(r).max(axis=1))) if len(r) else 1.0
    gaps = np.abs(logits.astype(np.float64) - r).max(axis=1) / max(unit, 1e-30)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def check(window, ref: np.ndarray, limits: Dict[str, float]) -> Dict[str, Dict]:
    """Each compared number beside its limit, in a fixed order."""
    gaps = logit_gaps(window.keys, window.logits, ref)
    return {
        "logit_err": {"value": float(gaps.max()) if len(gaps) else float("inf"),
                      "limit": limits["logit_err"]},
        "unmatched": {"value": int(window.unmatched), "limit": 0},
    }


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
