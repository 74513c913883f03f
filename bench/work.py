"""Operations and bytes a kernel call must move, and the roofline share.

These count what any implementation of the same answer has to do, not
what one kernel happens to do, so a kernel that reads less than today's
cannot read above 100%.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


def serve_call(q: int, d: int, cap: int, depth: int, itemsize: int,
               nprobe: int, distinct_clusters: int) -> tuple[float, float]:
    """(flops, bytes) of one two-stage call over ``q`` queries.

    Exact routing scores every prototype, so the whole index
    (``cap * d`` fp32) is read once, and the query block once. Each
    distinct routed cluster's ring tile (``depth * d`` rows in the store
    dtype) and its per-slot scale or bias row (``depth`` fp32) is read
    once, however many queries of the call route to it. Flops: route
    scores against every prototype, rerank scores against every routed
    slot."""
    nbytes = (cap * d * 4 + q * d * 4
              + distinct_clusters * (depth * d * itemsize + depth * 4))
    flops = 2 * q * cap * d + 2 * q * nprobe * depth * d
    return float(flops), float(nbytes)


def admit_call(b: int, k: int, n: int, d: int,
               itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one admission call over a ``b``-doc batch: the
    batch, the ``k`` centroids and the ``n`` basis rows read once; per
    doc one store row in the store dtype plus its scale, relevance and
    label written (12 bytes); scores against every centroid and basis
    row."""
    nbytes = b * d * 4 + k * d * 4 + n * d * 4 + b * (d * itemsize + 12)
    flops = 2 * b * (k + n) * d
    return float(flops), float(nbytes)


def min_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float,
                                                                   str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def routed_clusters(queries, vectors, valid, labels, nprobe: int) -> int:
    """Distinct clusters that ``queries`` route to: exact float64 cosine
    over the valid prototype rows, top ``nprobe`` slots, their labels."""
    q = np.asarray(queries, np.float64)
    q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    s = q @ np.asarray(vectors, np.float64).T
    s[:, ~np.asarray(valid, bool)] = -np.inf
    top = np.argsort(-s, axis=1, kind="stable")[:, :nprobe]
    routed = np.asarray(labels)[top][np.isfinite(
        np.take_along_axis(s, top, axis=1))]
    return int(np.unique(routed[routed >= 0]).size)
