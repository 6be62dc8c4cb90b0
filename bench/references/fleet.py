"""Plain reference of multi-tenant cosine top-k retrieval over INT8 codes.

The semantics the fleet promises: a query of tenant t gets k distinct
documents of t's own corpus, each with its exact INT8 dot product with
the query as its score, in order of cosine similarity (dot over the
document's norm), and those k are the tenant's top k, up to what the
configured cascade (cluster prune, sign prescreen, INT4 scan) loses.

The reference scores every document of the tenant exactly in numpy (int8
values are exact in float32 and every partial sum stays under 2**24), and
knows the codes from the seed, not from the program. `control_answers`
computes the same answers one precision lower, at INT4 (the most
significant nibble of each code), as the control that must fail.
"""
from __future__ import annotations

import numpy as np


def exact_scores(queries: np.ndarray, docs: np.ndarray) -> np.ndarray:
    """(Q, D) x (N, D) int8 -> (Q, N) int64 dot products, exactly."""
    return (queries.astype(np.float32) @ docs.astype(np.float32).T
            ).astype(np.int64)


def msb_nibble(codes: np.ndarray) -> np.ndarray:
    """Arithmetic shift right by four: the INT4 view of INT8 codes."""
    return (codes.astype(np.int16) >> 4).astype(np.int8)


def _keys(scores: np.ndarray, norms: np.ndarray) -> np.ndarray:
    return scores / np.sqrt(np.maximum(norms, 1).astype(np.float64))


def check_answers(codes: np.ndarray, slot_of: np.ndarray, asked,
                  answers, k: int, *, rel_tol: float = 1e-12) -> dict:
    """Hold served answers against the exact reference.

    codes: (T, N, D) int8 corpus from the seed; slot_of: (T, N) arena slot
    of each document; asked: (tenants (Q,), queries (Q, D) int8); answers:
    per request None (never answered) or (ids (k,), scores (k,)).
    """
    tenants, queries = asked
    t_count, n, _ = codes.shape
    doc_of = {}
    owner_t = np.repeat(np.arange(t_count), n)
    owner_d = np.tile(np.arange(n), t_count)
    flat = slot_of.reshape(-1)
    for s, tt, dd in zip(flat, owner_t, owner_d):
        doc_of[int(s)] = (int(tt), int(dd))
    norms = (codes.astype(np.int64) ** 2).sum(-1)
    out = dict(unanswered=0, bad_ids=0, leaks=0, score_mismatch=0,
               order_violations=0, topk_miss_share=0.0)
    misses = positions = 0
    for t in np.unique(tenants):
        rows = np.flatnonzero(tenants == t)
        s_all = exact_scores(queries[rows], codes[t])        # (q, N)
        key_all = _keys(s_all, norms[t][None, :])
        best = -np.sort(-key_all, axis=1)[:, :k]
        for r, row in enumerate(rows):
            ans = answers[row]
            if ans is None:
                out["unanswered"] += 1
                continue
            ids, scores = (np.asarray(a).reshape(-1) for a in ans)
            positions += k
            if (len(ids) != k or np.any(ids < 0)
                    or len(set(ids.tolist())) != k):
                out["bad_ids"] += 1
                misses += k
                continue
            docs = []
            for sid in ids.tolist():
                tt, dd = doc_of.get(int(sid), (-1, -1))
                if tt != t:
                    out["leaks"] += 1
                docs.append(dd if tt == t else -1)
            if any(d < 0 for d in docs):
                misses += k
                continue
            want = s_all[r, docs]
            out["score_mismatch"] += int(np.sum(want != scores))
            keys = key_all[r, docs]
            tol = rel_tol * np.maximum(np.abs(keys[:-1]), 1.0)
            out["order_violations"] += int(np.sum(keys[1:] > keys[:-1]
                                                  + tol))
            got = np.sort(keys)[::-1]
            tol = rel_tol * np.maximum(np.abs(best[r]), 1.0)
            misses += int(np.sum(got < best[r] - tol))
    out["topk_miss_share"] = misses / max(positions, 1)
    return out


def control_answers(codes: np.ndarray, slot_of: np.ndarray, asked,
                    k: int) -> list:
    """The reference one precision lower: INT4 scores and INT4 cosine
    ranking over the tenant's whole corpus, in the program's place."""
    tenants, queries = asked
    out = [None] * len(tenants)
    for t in np.unique(tenants):
        rows = np.flatnonzero(tenants == t)
        d4 = msb_nibble(codes[t])
        s = exact_scores(msb_nibble(queries[rows]), d4)
        key = _keys(s, (d4.astype(np.int64) ** 2).sum(-1)[None, :])
        top = np.argsort(-key, axis=1, kind="stable")[:, :k]
        for r, row in enumerate(rows):
            out[row] = (slot_of[t, top[r]], s[r, top[r]])
    return out
