"""Seeded word-mix texts: the live documents and the queries drawn from them.

Standard library only (the load generator imports it). Shapes are MS MARCO
passage ranking's published ones, drawn and not replayed (no network): passages
of 20-120 words, queries of 3-12. The *sizes* come from a fixed pool seed, so
every ``--seed`` offers the same lengths; the words come from ``seed``.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List


def draw_length(rng: random.Random, spec: Dict[str, Any]) -> int:
    """``min..max`` around ``mean``: a triangular draw keeps the range hard."""
    lo, hi, mean = spec["min"], spec["max"], spec["mean"]
    mode = min(max(3.0 * mean - lo - hi, lo), hi)
    return max(lo, min(hi, int(round(rng.triangular(lo, hi, mode)))))


def documents(corpus: Dict[str, Any], seed: int) -> List[str]:
    """The ``live_docs`` documents of one run. Document ``i`` starts with its own
    id (``doc<i>``), so a reply's text says which document it is."""
    sizes = random.Random(int(corpus.get("pool_seed", 0)))
    words = random.Random(seed)
    vocab = int(corpus["vocab_words"])
    out = []
    for i in range(int(corpus["live_docs"])):
        n = draw_length(sizes, corpus["doc_words"]) - 1
        out.append(" ".join([f"doc{i}"] + [f"w{words.randrange(vocab):05d}" for _ in range(n)]))
    return out


def query_from(doc: str, rng: random.Random, n_words: int, tag: str) -> str:
    """A query about one document: a contiguous span of its words (never its id
    word), closed by a running tag that makes every query text unique."""
    body = doc.split()[1:]
    n = min(n_words - 1, len(body))
    start = rng.randrange(len(body) - n + 1)
    return " ".join(body[start : start + n] + [tag])
