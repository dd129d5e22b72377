"""Plain reference of the host text ops of the refinement recipes.

Semantics as the recipe states them, written from their definitions:

- ``whitespace_normalization_mapper``: on each line, every run of spaces,
  tabs, form feeds and vertical tabs becomes one space, and trailing
  whitespace goes; lines are joined with ``\\n``;
- ``text_length_filter``: characters of the text (``text_len``);
- ``words_num_filter``: whitespace-separated words (``num_words``);
- ``alnum_ratio_filter``: share of characters that are alphanumeric or
  whitespace (``alnum_ratio``), 0 for an empty text;
- ``word_repetition_filter``: ``1 - distinct n-grams / n-grams`` over word
  n-grams (``word_rep_ratio``), 0 below ``n`` words.

A filter keeps a row when ``min_val <= stat <= max_val``. Filters commute,
so the kept set does not depend on their order.
"""
from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Tuple

_RUNS = re.compile(r"[ \t\f\v]+")


def normalize_whitespace(text: str) -> str:
    return "\n".join(_RUNS.sub(" ", line).rstrip() for line in text.splitlines())


def text_len(text: str) -> float:
    return float(len(text))


def num_words(text: str) -> float:
    return float(len(text.split()))


def alnum_ratio(text: str) -> float:
    if not text:
        return 0.0
    # no character is both alphanumeric and whitespace
    return (sum(map(str.isalnum, text)) + sum(map(str.isspace, text))) / len(text)


def word_rep_ratio(text: str, n: int) -> float:
    words = text.split()
    if len(words) < n:
        return 0.0
    grams = [tuple(words[i:i + n]) for i in range(len(words) - n + 1)]
    return 1.0 - len(set(grams)) / len(grams)


STATS = {
    "text_length_filter": ("text_len", lambda t, kw: text_len(t)),
    "words_num_filter": ("num_words", lambda t, kw: num_words(t)),
    "alnum_ratio_filter": ("alnum_ratio", lambda t, kw: alnum_ratio(t)),
    "word_repetition_filter": ("word_rep_ratio", lambda t, kw: word_rep_ratio(t, kw.get("n", 5))),
}
MAPPERS = {"whitespace_normalization_mapper": normalize_whitespace}


def host_steps(process: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The recipe's steps that this module covers, in recipe order."""
    return [s for s in process if s["name"] in STATS or s["name"] in MAPPERS]


def apply(text: str, steps: List[Dict[str, Any]]
          ) -> Tuple[str, Optional[Dict[str, float]]]:
    """Run one document through the host steps: ``(text, stats)``, with
    ``stats`` None when a filter drops it."""
    stats: Dict[str, float] = {}
    for step in steps:
        name = step["name"]
        if name in MAPPERS:
            text = MAPPERS[name](text)
            continue
        key, fn = STATS[name]
        v = fn(text, step)
        lo = step.get("min_val", -math.inf)
        hi = step.get("max_val", math.inf)
        if not lo <= v <= hi:
            return text, None
        stats[key] = v
    return text, stats
