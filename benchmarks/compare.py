"""The comparison that decides ``correct``: served replies against the reference.

Every number compared has a limit of its own, stated in the cell's file; the run
prints each beside its limit. ``compare`` is given answers, not a server, so the
same code judges the program, the lower-precision control and a planted fault.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

import numpy as np

Entry = Tuple[Optional[int], Optional[str], float]  # (live document or None, its text or None, score)
Answer = Optional[List[Entry]]  # one entry per rank; None: no reply


def parse_reply(body: Optional[str]) -> Answer:
    """A ``/v1/retrieve`` body as (document, text, score) per rank. A live
    document is named by its text's first word (``doc<i>``) and the whole text is
    kept to be compared with that document's. An entry with no text is a
    resident row (installed with no document behind it): only its score can be
    read. The score is the cosine (``-dist``)."""
    if body is None:
        return None
    try:
        out: List[Entry] = []
        for r in json.loads(body):
            text = r["text"]
            doc = None if text is None else int(str(text).split(" ", 1)[0][3:])
            out.append((doc, None if text is None else str(text), -float(r["dist"])))
        return out
    except (ValueError, KeyError, TypeError, IndexError):
        return None


def answers_from(ids: np.ndarray, scores: np.ndarray, docs: List[str]) -> List[Answer]:
    """Row ids over all rows (live documents first) and their scores, as served
    replies would carry them: what a control or a planted fault puts in the
    program's place."""
    return [[(int(i), docs[int(i)], float(s)) if i < len(docs) else (None, None, float(s))
             for i, s in zip(row_ids, row_scores)] for row_ids, row_scores in zip(ids, scores)]


def compare(answers: List[Answer], k: int, docs: List[str], ref_scores: np.ndarray,
            ref_topk: np.ndarray) -> Dict[str, float]:
    """``answers[r]`` is what was served for sampled query ``r``;
    ``ref_scores[r, d]`` the reference's cosine of query ``r`` with live document
    ``d``; ``ref_topk[r, j]`` the reference's j-th best cosine over ALL rows,
    live and resident.

    - ``bad_replies``: sampled replies that are missing, unreadable, not ``k``
      long or not ordered by score.
    - ``text_mismatch``: served entries whose text is not that document's text.
    - ``kth_score_err``: the widest gap between the score served at rank j and
      the reference's j-th best score over all rows. It holds every entry,
      resident rows too: a search that leaves out rows the reference finds
      serves lower scores from the rank of the first one left out.
    - ``score_err``: over entries that name a live document, the widest gap
      between the served score and the reference's score of that document;
      ``score_err_mean`` the mean gap, steady from seed to seed where the widest swings.
    - ``rank_gap``: over the same entries, the widest gap by which the document
      served at rank j scores, in the reference, below the reference's own j-th best.
    """
    bad = mismatch = 0
    kth_err = score_err = rank_gap = err_sum = 0.0
    n_scores = 0
    for r, ans in enumerate(answers):
        if ans is None or len(ans) != k or any(a[2] < b[2] for a, b in zip(ans, ans[1:])):
            bad += 1
            continue
        for j, (doc, text, score) in enumerate(ans):
            kth_err = max(kth_err, abs(score - float(ref_topk[r, j])))
            if doc is None:
                continue
            if not 0 <= doc < len(docs) or docs[doc] != text:
                mismatch += 1
                continue
            err = abs(score - float(ref_scores[r, doc]))
            score_err, err_sum, n_scores = max(score_err, err), err_sum + err, n_scores + 1
            rank_gap = max(rank_gap, float(ref_topk[r, j]) - float(ref_scores[r, doc]))
    return {"bad_replies": float(bad), "text_mismatch": float(mismatch), "kth_score_err": kth_err,
            "score_err": score_err, "score_err_mean": err_sum / max(n_scores, 1), "rank_gap": rank_gap}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each number beside its limit; correct when none is over. A number with no
    limit in the cell's file is an error, not a pass."""
    table = {name: {"value": value, "limit": float(limits[name])} for name, value in numbers.items()}
    return all(v["value"] <= v["limit"] for v in table.values()), table
