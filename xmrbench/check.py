"""The comparison that decides ``correct``: served answers against the
plain reference, query by query.

For each sampled answer whose reference search has no near-tie at a cut
(``ambiguous``: two candidates closer than ``TIE_RTOL`` on either side of
a beam or top-k cut, where f32 rounding may pick either), the served
labels must equal the reference's, up to the order inside groups of
near-tied positions; each served score is compared with the reference's
score of the same label. The numbers compared, each beside its limit:

``mismatched``     answers whose labels differ from the reference's.
                   Exact: limit 0.
``score_rel_err``  the largest ``|served - ref| / ref`` over the labels of
                   the answers that match; the limit is set in the
                   configuration file from calibration readings.

A request that never resolved is not in the sample; the harness counts it
as failed, and a run with any failure is not correct.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from xmrbench.references.xmr_tree import TIE_RTOL


def compare(got_s: np.ndarray, got_l: np.ndarray, ref,
            topk: int) -> Dict[str, float]:
    """Readings for the sampled answers ``got_s``/``got_l`` ([n, topk])
    against the reference result ``ref`` of the same queries."""
    mismatched, worst, checked = 0, 0.0, 0
    for q in range(got_l.shape[0]):
        if ref.ambiguous[q]:
            continue
        checked += 1
        rs, rl = ref.scores[q], ref.labels[q]
        start, ok = 0, True
        for i in range(1, topk + 1):
            near = i < topk and rs[i - 1] - rs[i] <= TIE_RTOL * rs[i - 1]
            if not near:
                if set(got_l[q, start:i].tolist()) != set(rl[start:i].tolist()):
                    ok = False
                    break
                start = i
        if not ok:
            mismatched += 1
            continue
        by_label = dict(zip(rl.tolist(), rs.tolist()))
        want = np.array([by_label[int(l)] for l in got_l[q]])
        err = np.abs(got_s[q].astype(np.float64) - want) / want
        worst = max(worst, float(np.max(err)))
    return {
        "mismatched": float(mismatched),
        "score_rel_err": worst,
        "checked": float(checked),
        "ambiguous": float(int(np.sum(ref.ambiguous))),
    }


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every limited reading is at or under its limit."""
    return all(readings[k] <= v for k, v in limits.items())
