"""The comparison that decides ``correct``.

Every request submitted in the window is judged once the window has
closed, against the plain reference (``lib/reference.py``) and the raw
corpus. The numbers compared, each against the cell's limit
(``limits/<cell>.json``):

- ``unanswered``: requests not answered in full (limit 0): refused at
  admission, failed, never answered, or served at a degrade rung other
  than rung 0, which the configuration rules out.
- ``bad_ids``: returned ids that are out of range, repeated within one
  answer, fail the request's filter, or come out of distance order
  (limit 0): exact verification, and each answer handed to its own
  request.
- ``dist_err``: the largest relative gap between a returned distance and
  the exact float64 distance of the same record to the request's query:
  the record fetch and the exact re-rank.
- ``recall_short``: 1 - mean recall@10 against the exact filtered
  top-10: the router's choice of mechanism, the hop loop and the
  pre-filter scan together.
"""
from __future__ import annotations

import numpy as np

from lib import reference
from lib.corpus import Corpus
from lib.traffic import Pool

NUMBERS = ("unanswered", "bad_ids", "dist_err", "recall_short")
CHUNK = 2048         # answers per block of the distance check


def judge(c: Corpus, pool: Pool, answers: list, n_unanswered: int,
          k: int) -> dict:
    """Readings of every number for ``answers``: (pool row, ids, dists)
    per answered request, ids -1 padded after the last result;
    ``n_unanswered`` requests were not answered in full."""
    if not answers:
        return {"unanswered": n_unanswered, "bad_ids": 0, "dist_err": 0.0,
                "recall_short": 1.0}
    a_rows = np.array([a[0] for a in answers], np.int64)
    ids = np.full((len(answers), k), -1, np.int64)
    dists = np.full((len(answers), k), np.inf)
    for i, (_, got, d) in enumerate(answers):
        got = np.asarray(got).reshape(-1)[:k]
        ids[i, :got.size] = got
        dists[i, :got.size] = np.asarray(d, np.float64).reshape(-1)[:k]
    live = ids >= 0
    bad = int(np.sum(live & (ids >= c.n)))
    live &= ids < c.n
    safe = np.where(live, ids, 0)

    srt = np.sort(np.where(live, ids, -1), axis=1)
    bad += int(np.sum((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)))
    tag, lo, hi = reference.filter_arrays(c, pool, a_rows)
    ok = (tag[:, None] < 0) | np.any(
        reference.padded_tags(c)[safe] == tag[:, None, None], axis=-1)
    vals = np.stack(list(c.values.values()), axis=1)[safe]
    ok &= np.all((vals >= lo[:, None, :]) & (vals < hi[:, None, :]), -1)
    bad += int(np.sum(live & ~ok))
    bad += int(np.sum(live[:, 1:] & live[:, :-1]
                      & (dists[:, 1:] < dists[:, :-1])))

    worst = 0.0
    q = c.queries[a_rows].astype(np.float64)
    for s in range(0, len(answers), CHUNK):
        diff = (c.vectors[safe[s:s + CHUNK]].astype(np.float64)
                - q[s:s + CHUNK, None, :])
        ref = np.einsum("akd,akd->ak", diff, diff)
        gap = np.abs(dists[s:s + CHUNK] - ref) / np.maximum(ref, 1e-6)
        lv = live[s:s + CHUNK]
        if lv.any():
            worst = max(worst, float(gap[lv].max()))

    uniq, inv = np.unique(a_rows, return_inverse=True)
    gt, _ = reference.exact_topk(c, pool, uniq, k)
    gt = gt[inv]
    gt_live = gt >= 0
    hit = np.any((gt[:, :, None] == np.where(live, ids, -2)[:, None, :]),
                 axis=-1) & gt_live
    n_gt = gt_live.sum(1)
    recall = np.where(n_gt > 0, hit.sum(1) / np.maximum(n_gt, 1), 1.0)
    return {"unanswered": n_unanswered, "bad_ids": bad, "dist_err": worst,
            "recall_short": 1.0 - float(recall.mean())}


def verdict(readings: dict, limits: dict) -> tuple:
    """(correct, [[name, reading, limit], ...]) - a reading passes when it
    is at most its limit."""
    rows = [[name, readings[name], limits[name]] for name in NUMBERS]
    return all(r <= lim for _, r, lim in rows), rows
