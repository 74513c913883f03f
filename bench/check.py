"""The plain references that decide ``correct``, and their controls.

Nothing here imports the program. Every reference is float64 numpy over
what the benchmark itself made (the stream, the queries) and what the
program published (snapshots, the state around sampled ingest calls).

Queries: each checked two-stage answer is compared with exact cosine
top-k over the live rows of the clusters that the snapshot it names
routes to (top-nprobe over the valid prototype rows). Routing near-ties
are allowed either way: a cluster whose route score lies within
``2 * ROUTE_TOL`` of the nprobe boundary may or may not be routed. A
prototype-only answer is compared with exact top-k over the valid
prototype rows.

Ingest: each sampled ingest call is replayed from the state before it:
the relevance screen against the topic basis, the nearest centroid, the
quantized store row, the counter update (the configuration keeps a
counter slot for every cluster, so every kept arrival is a hit or an
insert) and the ring write. The store published after the window is
checked row by row against the docs the benchmark sent.

The controls are the same references computed in bfloat16 (operands and
results rounded to bfloat16, products accumulated in float32), put in
the program's place: they must come out as not correct.
"""
from __future__ import annotations

import numpy as np

# The TPU's matrix unit rounds float32 operands to bfloat16 at default
# precision: a dot of two unit vectors is then off by at most
# 2 * 2^-9 * sum|q_i e_i| <= 2^-8 (Cauchy-Schwarz). Route scores, the
# relevance screen and cluster assignment may each be that far from
# float64; decisions closer than this to their threshold may go either way.
ROUTE_TOL = 2.0 ** -8


def _unit(x):
    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def bf16(x):
    """Round to bfloat16 and back to float32."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def quantize_rows(v: np.ndarray):
    """Symmetric per-row int8: scale = max|v| / 127, round half to even."""
    v = np.asarray(v, np.float64)
    scale = np.maximum(np.max(np.abs(v), axis=-1), 1e-12) / 127.0
    q = np.clip(np.round(v / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale


# ------------------------------------------------------------------ queries
def host_snapshot(snap) -> dict:
    """The leaves of a published snapshot the query reference reads."""
    import jax

    index, labels, store = jax.device_get(
        (snap.index, snap.route_labels, snap.store))
    return {"vectors": np.asarray(index.vectors, np.float64),
            "valid": np.asarray(index.valid, bool),
            "index_ids": np.asarray(index.ids),
            "labels": np.asarray(labels), "ids": np.asarray(store.ids),
            "embs": np.asarray(store.embs), "scales": np.asarray(
                store.scales, np.float64)}


def _rows(snap: dict, c: int, rows: np.ndarray) -> np.ndarray:
    e = np.asarray(snap["embs"][c, rows], np.float64)
    if snap["embs"].dtype == np.int8:
        e = e * snap["scales"][c, rows][:, None]
    return e


def check_answers(answers: list, queries: np.ndarray, snap: dict, k: int,
                  nprobe: int) -> dict:
    """Compare answers ``[(doc_ids, scores, clusters)]`` to ``queries``
    with exact top-k on ``snap``.

    Returns the numbers compared: ``wrong_answers`` (an answer with a
    duplicate, a doc outside every routable cluster, a wrong cluster, or
    fewer results than any tie-aware routing leaves), ``rank_gap`` (the
    widest gap by which the exact score of the j-th served doc lies below
    the reference's j-th best) and ``score_err`` (the widest gap between
    a served score and the doc's exact score)."""
    qn = _unit(queries)
    s1 = qn @ snap["vectors"].T
    s1[:, ~snap["valid"]] = -np.inf
    wrong, rank_gap, score_err, no_sure = 0, 0.0, 0.0, 0
    for i, (ids, scores, clusters) in enumerate(answers):
        order = np.argsort(-s1[i], kind="stable")
        nth = s1[i, order[nprobe - 1]]
        nxt = s1[i, order[nprobe]] if order.size > nprobe else -np.inf
        maybe = np.nonzero(s1[i] >= nth - 2 * ROUTE_TOL)[0]
        exact, cluster_of, sure, unsure = {}, {}, {}, []
        for slot in maybe:
            c = int(snap["labels"][slot])
            if c < 0 or not np.isfinite(s1[i, slot]):
                if not s1[i, slot] > nxt + 2 * ROUTE_TOL:
                    unsure.append((None, 0))    # routable, holds nothing
                continue
            rows = np.nonzero(snap["ids"][c] >= 0)[0]
            sc = _rows(snap, c, rows) @ qn[i]
            doc = snap["ids"][c, rows].tolist()
            exact.update(zip(doc, sc.tolist()))
            cluster_of.update(dict.fromkeys(doc, c))
            if s1[i, slot] > nxt + 2 * ROUTE_TOL:
                sure[c] = sc
            else:
                unsure.append((c, rows.size))
        certain = [v for sc in sure.values() for v in sc.tolist()]
        # The fewest candidates any tie-aware routing leaves: the certain
        # clusters' rows, and the rows of the emptiest unsure slots that
        # fill nprobe (a slot whose cluster is counted already adds none).
        seen, adds = set(sure), []
        for c, n in unsure:
            adds.append(0 if c in seen else n)
            seen.add(c)
        n_sure = int(np.sum(s1[i, maybe] > nxt + 2 * ROUTE_TOL))
        fewest = len(certain) + sum(sorted(adds)[:max(0, nprobe - n_sure)])
        no_sure += not sure
        ref = np.sort(np.asarray(certain))[::-1][:k]
        got = np.asarray(ids)
        keep = got >= 0
        g_ids = got[keep].tolist()
        g_sc = np.asarray(scores)[keep]
        g_cl = np.asarray(clusters)[keep].tolist()
        bad = len(set(g_ids)) != len(g_ids)
        bad |= any(d not in exact or cluster_of[d] != c
                   for d, c in zip(g_ids, g_cl))
        bad |= not min(k, fewest) <= len(g_ids) <= min(k, len(exact))
        if bad:
            wrong += 1
            continue
        e = np.asarray([exact[d] for d in g_ids])
        if ref.size:
            served = np.full(ref.size, -np.inf)
            served[:min(ref.size, e.size)] = e[:ref.size]
            rank_gap = max(rank_gap, float(np.max(ref - served)))
        if e.size:
            score_err = max(score_err, float(np.max(np.abs(e - g_sc))))
    return {"checked": len(answers), "wrong_answers": wrong,
            "rank_gap": rank_gap, "score_err": score_err,
            "no_certain_cluster": no_sure}


def check_prototype_answers(answers: list, queries: np.ndarray,
                            snap: dict, k: int) -> dict:
    """Compare prototype-only answers ``[(doc_ids, scores, clusters)]``
    with exact top-k over the valid index rows of ``snap``: an answer is
    wrong if it holds a (cluster, representative doc) pair that no valid
    row holds, a row twice, or other than ``min(k, valid rows)`` results;
    ``rank_gap`` and ``score_err`` are read as for two-stage answers."""
    qn = _unit(queries)
    valid = np.nonzero(snap["valid"])[0]
    s1 = qn @ snap["vectors"][valid].T
    row_of = {(int(snap["labels"][v]), int(snap["index_ids"][v])): j
              for j, v in enumerate(valid)}
    wrong, rank_gap, score_err = 0, 0.0, 0.0
    for i, (ids, scores, clusters) in enumerate(answers):
        keep = np.asarray(clusters) >= 0
        pairs = list(zip(np.asarray(clusters)[keep].tolist(),
                         np.asarray(ids)[keep].tolist()))
        if (len(set(pairs)) != len(pairs) or len(pairs) != min(k, valid.size)
                or any(p not in row_of for p in pairs)):
            wrong += 1
            continue
        if pairs:
            e = s1[i, [row_of[p] for p in pairs]]
            ref = np.sort(s1[i])[::-1][:len(pairs)]
            rank_gap = max(rank_gap, float(np.max(ref - e)))
            score_err = max(score_err, float(np.max(np.abs(
                e - np.asarray(scores)[keep]))))
    return {"checked": len(answers), "wrong_answers": wrong,
            "rank_gap": rank_gap, "score_err": score_err,
            "no_certain_cluster": 0}


def control_prototype_answers(queries: np.ndarray, snap: dict,
                              k: int) -> list:
    """Prototype-only answers computed in bfloat16, in the program's
    place."""
    s1 = bf16(bf16(_unit(queries)) @ bf16(snap["vectors"]).T)
    s1[:, ~snap["valid"]] = -np.inf
    out = []
    for i in range(len(queries)):
        rows = [r for r in np.argsort(-s1[i], kind="stable")[:k]
                if np.isfinite(s1[i, r])]
        pad = k - len(rows)
        out.append((np.asarray([snap["index_ids"][r] for r in rows]
                               + [-1] * pad),
                    np.asarray([s1[i, r] for r in rows] + [-np.inf] * pad,
                               np.float32),
                    np.asarray([snap["labels"][r] for r in rows]
                               + [-1] * pad)))
    return out


def control_answers(queries: np.ndarray, snap: dict, k: int,
                    nprobe: int) -> list:
    """The reference's answers computed in bfloat16, in the program's
    place: route scores and ring scores rounded to bfloat16 (the int8
    dequantization scale applied in bfloat16), ties to the lowest route
    position and slot, as the program breaks them."""
    qb = bf16(_unit(queries))
    s1 = bf16(qb @ bf16(snap["vectors"]).T)
    s1[:, ~snap["valid"]] = -np.inf
    out = []
    for i in range(len(queries)):
        slots = np.argsort(-s1[i], kind="stable")[:nprobe]
        ids, scs, cls = [], [], []
        for slot in slots:
            c = int(snap["labels"][slot])
            if c < 0 or not np.isfinite(s1[i, slot]):
                continue
            rows = np.nonzero(snap["ids"][c] >= 0)[0]
            e = np.asarray(snap["embs"][c, rows], np.float32)
            sc = bf16(bf16(e) @ qb[i])
            if snap["embs"].dtype == np.int8:
                sc = bf16(sc * bf16(snap["scales"][c, rows]))
            ids.extend(snap["ids"][c, rows].tolist())
            scs.extend(sc.tolist())
            cls.extend([c] * rows.size)
        order = np.argsort(-np.asarray(scs), kind="stable")[:k]
        pad = k - order.size
        out.append((np.asarray([ids[j] for j in order] + [-1] * pad),
                    np.asarray([scs[j] for j in order] + [-np.inf] * pad,
                               np.float32),
                    np.asarray([cls[j] for j in order] + [-1] * pad)))
    return out


# ------------------------------------------------------------------- ingest
def reference_admit(x, basis, centroids, alpha: float, quantized: bool):
    """float64 admission of one batch: relevance, keep, nearest centroid,
    the store row; each with a flag for decisions inside ROUTE_TOL."""
    xn = _unit(x)
    r = np.mean(xn @ _unit(basis).T, axis=1)
    sims = xn @ _unit(centroids).T
    top2 = np.partition(sims, -2, axis=1)[:, -2:]
    label = np.argmax(sims, axis=1)
    out = {"keep": r >= alpha, "keep_unsure": np.abs(r - alpha) <= ROUTE_TOL,
           "label": label,
           "label_unsure": (top2[:, 1] - top2[:, 0]) <= 2 * ROUTE_TOL}
    if quantized:
        out["q"], out["scale"] = quantize_rows(xn)
    else:
        out["v"] = xn
    return out


def control_admit(x, basis, centroids, alpha: float, quantized: bool):
    """The same admission computed in bfloat16 (no tie flags: these are
    the decisions the control puts in the program's place)."""
    xb = bf16(x)
    xn = bf16(xb / bf16(np.sqrt(np.sum(xb * xb, axis=1, keepdims=True))))
    vb = bf16(_unit(basis))
    cb = bf16(_unit(centroids))
    r = bf16(np.mean(bf16(xn @ vb.T), axis=1))
    label = np.argmax(bf16(xn @ cb.T), axis=1)
    out = {"keep": r >= alpha, "label": label}
    if quantized:
        scale = bf16(np.max(np.abs(xn), axis=1) / 127.0)
        out["q"] = np.clip(np.round(bf16(xn / scale[:, None])), -127,
                           127).astype(np.int8)
        out["scale"] = scale.astype(np.float64)
    else:
        out["v"] = xn
    return out


def apply_admit(before: dict, decided: dict, ids: np.ndarray, depth: int,
                quantized: bool) -> dict:
    """The state after a batch whose keep/label/rows are ``decided``, by
    the ring-write and counter semantics (the control's 'program')."""
    after = {"store.ids": before["store.ids"].copy(),
             "store.ptr": before["store.ptr"].copy(),
             "hh.labels": before["hh.labels"].copy(),
             "hh.counts": before["hh.counts"].copy()}
    k, _ = after["store.ids"].shape
    d = decided["q" if quantized else "v"].shape[1]
    embs = np.zeros((k, depth, d), np.int8 if quantized else np.float32)
    scales = np.zeros((k, depth))
    written = np.zeros((k, depth), bool)
    tracked = set(after["hh.labels"].tolist())
    for i in np.nonzero(decided["keep"])[0]:
        c = int(decided["label"][i])
        slot = after["store.ptr"][c] % depth
        after["store.ids"][c, slot] = ids[i]
        after["store.ptr"][c] += 1
        written[c, slot] = True
        if quantized:
            embs[c, slot], scales[c, slot] = decided["q"][i], \
                decided["scale"][i]
        else:
            embs[c, slot] = decided["v"][i]
        if c not in tracked:
            tracked.add(c)
            free = np.nonzero(after["hh.labels"] < 0)[0][0]
            after["hh.labels"][free] = c
            after["hh.counts"][free] = 0
        after["hh.counts"][after["hh.labels"] == c] += 1
    after["store.embs"] = embs
    after["store.scales"] = scales
    return after


def check_ingest_call(before: dict, after: dict, x: np.ndarray,
                      ids: np.ndarray, alpha: float, depth: int,
                      quantized: bool) -> dict:
    """Replay one ingest call from ``before`` and compare with ``after``.

    Returns ``docs_misplaced`` (a doc stored though certainly not kept,
    stored under a certainly wrong cluster, or certainly kept and not
    stored without ``depth`` later docs of its cluster in the batch to
    overwrite it), ``ring_slots_wrong`` (ring slots or write pointers
    that differ from the ring write of the batch's stored docs),
    ``hh_wrong`` (a kept doc's cluster left untracked by the counter, or
    a counter total that did not grow by one per kept doc) and the row
    element counts behind ``row_mismatch_share``."""
    ref = reference_admit(x, before["pre.basis"], before["clus.centroids"],
                          alpha, quantized)
    n = len(ids)
    pos = {int(d): i for i, d in enumerate(ids.tolist())}
    b_ids, a_ids = before["store.ids"], after["store.ids"]
    observed = {}                       # batch position -> (cluster, slot)
    for c, s in zip(*np.nonzero(np.isin(a_ids, ids) & (a_ids >= 0))):
        observed[pos[int(a_ids[c, s])]] = (int(c), int(s))
    sure_keep = ref["keep"] & ~ref["keep_unsure"]
    sure_drop = ~ref["keep"] & ~ref["keep_unsure"]
    sure_label = ~ref["label_unsure"]
    members: dict = {}                  # cluster -> batch positions
    for i in range(n):
        if i in observed:
            members.setdefault(observed[i][0], set()).add(i)
        elif sure_keep[i] and sure_label[i]:
            members.setdefault(int(ref["label"][i]), set()).add(i)
    b_ptr = before["store.ptr"].astype(np.int64)
    a_ptr = after["store.ptr"].astype(np.int64)
    grown = a_ptr - b_ptr               # docs the program stored, per cluster
    unsure_unseen = int(np.sum((ref["keep_unsure"] | ref["label_unsure"])
                               & ~np.isin(np.arange(n), list(observed))))
    misplaced = 0
    for i, (c, _) in observed.items():
        if sure_drop[i] or (sure_label[i] and c != ref["label"][i]):
            misplaced += 1
    for i in np.nonzero(sure_keep & sure_label)[0]:
        if i in observed:
            continue
        c = int(ref["label"][i])
        # absent is right only if overwritten: at least ``depth`` docs of
        # its cluster were stored after it
        before_i = sum(1 for j in members.get(c, ()) if j < i)
        if before_i >= grown[c] - depth:
            misplaced += 1
    # ring write: the last min(grown, depth) positions from the old write
    # pointer hold this batch's docs in arrival order; no other slot moved
    ring_wrong = int(np.sum(grown < 0))
    for c in range(b_ids.shape[0]):
        m = int(grown[c])
        if m <= 0:
            ring_wrong += int(np.sum(a_ids[c] != b_ids[c]))
            continue
        written = [(b_ptr[c] + r) % depth for r in range(max(0, m - depth), m)]
        rest = np.setdiff1d(np.arange(depth), written)
        ring_wrong += int(np.sum(a_ids[c, rest] != b_ids[c, rest]))
        got = [pos.get(int(d), -1) for d in a_ids[c, written]]
        ring_wrong += sum(1 for j in got if j < 0)
        ring_wrong += int(np.sum(np.diff(got) <= 0))
        lo = len(members.get(c, ()))
        if not lo <= m <= lo + unsure_unseen:
            ring_wrong += 1
    # counters: every kept doc is a hit or an insert (one count each)
    kept_lo = int(np.sum(sure_keep))
    kept_hi = kept_lo + int(np.sum(ref["keep_unsure"]))
    grew = int(np.sum(after["hh.counts"].astype(np.int64))
               - np.sum(before["hh.counts"].astype(np.int64)))
    hh_wrong = int(not kept_lo <= grew <= kept_hi)
    tracked = set(after["hh.labels"].tolist())
    hh_wrong += sum(1 for i in np.nonzero(sure_keep & sure_label)[0]
                    if int(ref["label"][i]) not in tracked)
    # rows of the stored docs
    el_bad = el_all = 0
    for i, (c, s) in observed.items():
        el_bad += row_mismatch(after, c, s, ref, i, quantized)
        el_all += x.shape[1]
    return {"docs": n, "stored": len(observed), "docs_misplaced": misplaced,
            "ring_slots_wrong": ring_wrong, "hh_wrong": hh_wrong,
            "row_elements": el_all, "row_elements_wrong": el_bad}


def check_published(after: dict, published: dict, ids: np.ndarray,
                    depth: int, quantized: bool) -> int:
    """Docs of one ingest call that the state right after it holds but
    the first snapshot published after the call does not hold, at the
    same slot with the same row, though no later write could have
    overwritten them. ``published`` holds that snapshot's store leaves."""
    a_ids, p_ids = after["store.ids"], published["store.ids"]
    a_ptr = after["store.ptr"].astype(np.int64)
    p_ptr = published["store.ptr"].astype(np.int64)
    rows = ("store.embs", "store.scales") if quantized else ("store.embs",)
    missing = 0
    for c, s in zip(*np.nonzero(np.isin(a_ids, ids) & (a_ids >= 0))):
        # the write count at which this slot was last written
        w = a_ptr[c] - 1 - (a_ptr[c] - 1 - s) % depth
        if p_ptr[c] < a_ptr[c]:
            missing += 1                # the snapshot lags the call
        elif w >= p_ptr[c] - depth and (
                p_ids[c, s] != a_ids[c, s]
                or any(np.any(published[r][c, s] != after[r][c, s])
                       for r in rows)):
            missing += 1
    return missing


def row_mismatch(store: dict, c: int, s: int, ref: dict, i: int,
                 quantized: bool) -> int:
    """Elements of the stored row ``(c, s)`` that differ from the
    reference row ``i``: int8 values that differ, all of them when the
    scale is off by more than float32 rounding; float32 values more than
    1e-6 off."""
    if quantized:
        scale = float(store["store.scales"][c, s])
        if abs(scale - ref["scale"][i]) > 1e-6 * ref["scale"][i]:
            return ref["q"].shape[1]
        return int(np.sum(store["store.embs"][c, s] != ref["q"][i]))
    return int(np.sum(np.abs(np.asarray(store["store.embs"][c, s],
                                        np.float64) - ref["v"][i]) > 1e-6))


def check_final_store(store: dict, lookup, depth: int,
                      quantized: bool) -> dict:
    """Check the store published after the window: every live slot that
    holds a doc the window sent carries that doc's row, no doc is held
    twice, and each ring's docs grow newer in write order. ``lookup``
    maps an array of doc ids to (mask of window docs, their rows)."""
    ids = store["store.ids"]
    ptr = store["store.ptr"]
    live = ids[ids >= 0]
    wrong = int(live.size - np.unique(live).size)
    for c in np.nonzero(ptr > 0)[0]:
        n = min(int(ptr[c]), depth)
        slots = [(int(ptr[c]) - 1 - j) % depth for j in range(n)]
        wrong += int(np.sum(np.diff(ids[c, slots]) >= 0))
    cs, ss = np.nonzero(ids >= 0)
    mine, x = lookup(ids[cs, ss])
    cs, ss = cs[mine], ss[mine]
    el_bad = 0
    if cs.size:
        ref = reference_rows(x, quantized)
        got = np.asarray(store["store.embs"][cs, ss])
        if quantized:
            scale = np.asarray(store["store.scales"][cs, ss], np.float64)
            off = np.abs(scale - ref["scale"]) > 1e-6 * ref["scale"]
            el_bad = int(np.sum(got[~off] != ref["q"][~off])
                         + np.sum(off) * got.shape[1])
        else:
            el_bad = int(np.sum(np.abs(got.astype(np.float64) - ref["v"])
                                > 1e-6))
    return {"ring_slots_wrong": wrong, "stored": int(cs.size),
            "row_elements": int(cs.size) * (x.shape[1] if cs.size else 0),
            "row_elements_wrong": el_bad}


def reference_rows(x: np.ndarray, quantized: bool) -> dict:
    xn = _unit(x)
    if quantized:
        q, scale = quantize_rows(xn)
        return {"q": q, "scale": scale}
    return {"v": xn}
