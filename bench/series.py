"""The host-clock series the end-to-end metrics are read from."""
from __future__ import annotations

import numpy as np


def query_latency_ms(run):
    """Due-to-answer milliseconds of every query due in the window, or
    None when the cell sends no queries. An unanswered query reads
    infinite."""
    if run.q_due is None or not len(run.q_due):
        return None
    lat = (run.q_done - (run.t_start + run.q_due)) * 1e3
    return np.where(np.isnan(lat), np.inf, lat)


def freshness_ms(run):
    """For every open-loop ingest batch due in the window: milliseconds
    from its due time to the first publication that covers its docs
    (infinite if none did)."""
    if run.b_due is None or not len(run.b_due):
        return None
    pubs = run.engine.publishes
    docs = np.asarray([p[2] for p in pubs], np.int64)
    times = np.asarray([p[1] for p in pubs])
    need = run.b_docs_before + run.b_size * (np.arange(len(run.b_due)) + 1)
    at = np.searchsorted(docs, need, side="left")
    covered = at < len(docs)
    t_pub = np.where(covered, times[np.minimum(at, len(docs) - 1)], np.inf)
    return (t_pub - (run.t_start + run.b_due)) * 1e3


def published_docs_per_s(run):
    """Docs made queryable per second: between the first and the last
    publication inside the window, the docs they cover over the time
    between them (whole publish intervals, so the cadence does not
    quantize the rate)."""
    t1 = run.t_start + run.seconds
    inside = [p for p in run.engine.publishes if run.t_start <= p[1] <= t1]
    if len(inside) < 2:
        return None
    (_, ta, da), (_, tb, db) = inside[0], inside[-1]
    return (db - da) / (tb - ta)
