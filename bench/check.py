"""The comparison that decides ``correct``.

Every request the timed window completed is run again through the
configuration's plain reference, from the same integer inputs, weights and
tables, and its top-layer ``h`` sequence and every layer's final ``h`` and
``c`` are compared integer by integer.  The datapath is exact integer
arithmetic, so every limit is 0.
"""

from __future__ import annotations

import collections

import numpy as np


def reference_outputs(ref, dp, weights, qxs_list):
    """The reference's ``(h_seq, qh, qc)`` per request, batched by length."""
    ws = [np.asarray(w) for w, _ in weights]
    bs = [np.asarray(b) for _, b in weights]
    by_len = collections.defaultdict(list)
    for i, q in enumerate(qxs_list):
        by_len[len(q)].append(i)
    out = [None] * len(qxs_list)
    for idx in by_len.values():
        seq, hs, cs = ref.forward(dp, ws, bs, np.stack([qxs_list[i] for i in idx]))
        for j, i in enumerate(idx):
            out[i] = (seq[j], hs[:, j], None if cs is None else cs[:, j])
    return out


def served_outputs(stream, n_layers: int):
    """A finished stream's ``(h_seq, qh, qc)``, state as ``(L, H)``."""
    qh = np.asarray(stream.qh).reshape(n_layers, -1)
    qc = None if stream.qc is None else np.asarray(stream.qc).reshape(n_layers, -1)
    return np.asarray(stream.h_seq), qh, qc


def compare(served, wanted) -> dict:
    """Numbers compared, each ``{"value", "limit", "rule"}``: integers that
    differ, the widest gap between two integers, requests without an answer
    (all at most 0), and requests compared (at least 1)."""
    mismatched = 0
    gap = 0
    unanswered = 0
    for got, want in zip(served, wanted):
        if got is None:
            unanswered += 1
            continue
        for g, w in zip(got, want):
            if w is None and g is None:
                continue
            if g is None or w is None or np.shape(g) != np.shape(w):
                mismatched += int(np.size(w if w is not None else g))
                continue
            d = np.abs(np.asarray(g, np.int64) - np.asarray(w, np.int64))
            mismatched += int(np.count_nonzero(d))
            gap = max(gap, int(d.max(initial=0)))
    return {
        "mismatched_ints": {"value": mismatched, "limit": 0, "rule": "<="},
        "max_int_gap": {"value": gap, "limit": 0, "rule": "<="},
        "unanswered": {"value": unanswered, "limit": 0, "rule": "<="},
        "compared": {"value": len(served) - unanswered, "limit": 1, "rule": ">="},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if c["rule"] == "<=" else
               c["value"] >= c["limit"] for c in checks.values())


def format_checks(checks: dict) -> str:
    return "; ".join(f"{k} {c['value']} (limit {c['rule']} {c['limit']})"
                     for k, c in checks.items())
