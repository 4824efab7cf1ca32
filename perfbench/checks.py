"""The numbers that decide `correct`, each held to the limit in the cell's
limits file (`perfbench/limits/<cell>.json`).

Training, against the reference's job from the same seed:
  loss_gap    worst relative gap of the first epochs' losses;
  grad_gap    worst leaf's gap between the norms of the first epoch's
              change (the SGD step times lr), program against reference;
  change_gap  the same after the last compared epoch;
  state_gap   worst leaf's norm of the difference of the two final states.
Each leaf's gap is measured against the reference's norm of that leaf's
change or the median leaf's, whichever is larger; a leaf whose first
change in the reference is under a thousandth of the median leaf's is
left out (it moves by rounding alone).

Serving, per sampled request, against the reference slate:
  bad_slates  slates that are structurally wrong: a popularity slate where
              a ranked one is due or the reverse, a popularity slate that
              differs, an id outside the user's unseen home-city POIs, an id
              twice, an empty slot where an item is left or the reverse, or
              items whose score is exactly 0 (p + q left at zero) served
              other than as the lowest such ids in ascending order;
  rank_gap    widest gap, over the ranked slots, by which the served item's
              reference score lies below the reference's score at that rank;
  score_gap   widest gap between a served score and the reference's score
              of the same item.
Both gaps are in units of the row's magnitude max_j sum_k |u_k v_jk|.
"""
from __future__ import annotations

import numpy as np

MISSING = 1e9          # a number that no limit admits


def leaf_gap(prog, ref, scale_ref) -> float:
    prog, ref, scale_ref = (np.asarray(a, np.float64) for a in
                            (prog, ref, scale_ref))
    keep = scale_ref >= 1e-3 * np.median(scale_ref)
    den = np.maximum(scale_ref, np.median(scale_ref))
    return float(np.max((np.abs(prog - ref) / den)[keep]))


def train_numbers(prog: dict, ref: dict, dists) -> dict:
    """``prog``/``ref``: losses, d1 (leaf change norms after the first
    epoch), d_end (after the last compared one); ``dists``: per-leaf norms
    of the final state's difference."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    n = min(len(lp), len(lr))
    if n < len(lr) or not np.isfinite(lp[:n]).all():
        loss = MISSING
    else:
        loss = float(np.max(np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n])))
    d_ref = np.asarray(ref["d_end"], np.float64)
    keep = np.asarray(ref["d1"]) >= 1e-3 * np.median(ref["d1"])
    den = np.maximum(d_ref, np.median(d_ref))
    state = float(np.max((np.asarray(dists, np.float64) / den)[keep]))
    out = {"loss_gap": loss,
           "grad_gap": leaf_gap(prog["d1"], ref["d1"], ref["d1"]),
           "change_gap": leaf_gap(prog["d_end"], ref["d_end"], ref["d_end"]),
           "state_gap": state}
    return {k: (v if np.isfinite(v) else MISSING) for k, v in out.items()}


def serve_numbers(served_ids, served_vals, ref_ids, fallback, pop_ids,
                  pop_vals, cand, scores, mag) -> dict:
    """All arrays row-aligned over the sampled requests: served (n, k) ids
    and scores; the reference's (n, k) ids, popularity rows, popularity
    slate; each row's (n, C) eligible ids, their reference scores and the
    row magnitude."""
    n, k = served_ids.shape
    bad = 0
    rank_gap = 0.0
    score_gap = 0.0
    for r in range(n):
        ids, vals = served_ids[r], served_vals[r]
        if fallback[r]:
            if not (np.array_equal(ids, pop_ids)
                    and np.array_equal(vals, pop_vals)):
                bad += 1
            continue
        ok = cand[r] >= 0
        elig, sc = cand[r][ok], scores[r][ok]
        want = int((ref_ids[r] >= 0).sum())
        filled = ids[:want]
        if ((ids[want:] != -1).any() or (filled < 0).any()
                or len(np.unique(filled)) != want
                or not np.isin(filled, elig).all()):
            bad += 1
            continue
        if want == 0:
            continue
        pos = np.searchsorted(elig, filled)
        got = sc[pos].astype(np.float64)
        tied = filled[got == 0.0]
        if not np.array_equal(tied, elig[sc == 0.0][:len(tied)]):
            bad += 1
            continue
        best = np.sort(sc.astype(np.float64))[::-1][:want]
        unit = max(float(mag[r]), np.finfo(np.float32).tiny)
        rank_gap = max(rank_gap, float(np.max(best - got)) / unit)
        score_gap = max(score_gap, float(np.max(
            np.abs(vals[:want].astype(np.float64) - got))) / unit)
    return {"bad_slates": float(bad), "rank_gap": rank_gap,
            "score_gap": score_gap}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct iff none is over it."""
    table = {name: {"value": float(numbers[name]), "limit": float(lim)}
             for name, lim in limits.items()}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table
