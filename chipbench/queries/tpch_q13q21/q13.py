"""Plain reference for q13.sql: numpy over the Parquet columns, the LIKE
read with Python's ``re``.  An order counts for its customer when its
comment does not match ``%special%requests%`` (a NULL comment matches
neither way: NOT LIKE of NULL is NULL, so the ON clause drops it); a
customer with no such order counts 0 (``count`` of the NULL a LEFT
OUTER join pads with).  Counts only: every cell is exact in either
precision."""
import re

import numpy as np

PATTERN = "%special%requests%"


def like_regex(pattern: str):
    """SQL LIKE (``%`` and ``_``, no escape) as a compiled ``re``
    pattern for ``fullmatch``."""
    return re.compile("".join(".*" if ch == "%" else "." if ch == "_"
                              else re.escape(ch) for ch in pattern),
                      re.DOTALL)


def answer(t, num):
    c, o = t["customer"], t["orders"]
    rx = like_regex(PATTERN)
    comment = o["o_comment"]
    matches = np.array([rx.fullmatch(v) is not None for v in comment.cats],
                       bool)
    kept = ~matches[comment.codes] if len(comment.cats) else \
        np.zeros(len(comment.codes), bool)
    row = num.lookup(c["c_custkey"], o["o_custkey"][kept])
    per_customer = np.bincount(row[row >= 0], minlength=len(c["c_custkey"]))
    counts, custdist = np.unique(per_customer, return_counts=True)
    top = sorted(zip(counts.tolist(), custdist.tolist()),
                 key=lambda r: (-r[1], -r[0]))
    return [(int(k), int(d)) for k, d in top]
