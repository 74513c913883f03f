"""jit'd public wrapper for routed gather-rerank (two-stage stage 2)."""
from __future__ import annotations

import jax.numpy as jnp

from repro import obs
from repro.kernels.common import on_tpu, use_pallas_default
from repro.kernels.rerank.ref import rerank_topk_ref


def rerank_topk(
    q: jnp.ndarray,
    embs: jnp.ndarray,
    live: jnp.ndarray,
    routes: jnp.ndarray,
    k: int,
    *,
    scales: jnp.ndarray | None = None,
    use_pallas: bool | None = None,
):
    """Exact top-k rerank of each query's routed cluster ring buffers.

    q [Q, d]; embs [C, depth, d] (f32, or i8 with per-slot ``scales``
    [C, depth] f32 — the quantized store layout); live [C, depth] bool;
    routes [Q, P] i32 cluster ids per query (-1 = no route);
    k <= P*depth. int8 rings are dequantized inside the kernel with fp32
    accumulation — no fp32 candidate tensor is materialized.

    Returns (scores [Q, k] f32 desc, pos [Q, k] i32) where pos encodes
    ``j * depth + slot`` into the query's route list (-1 = dead entry).
    Callers recover the document as
    ``cluster = routes[q, pos // depth]; slot = pos % depth``.

    Only the staged reference paths call this (the served two-stage query
    is the fused ``serve`` kernel). The Pallas kernel's (1, dp, d) and
    (1, k) blocks are not legal TPU block shapes, so on a TPU every call
    runs the jnp oracle and is counted as ``rerank_ref``; the kernel runs
    only in interpret mode, where its parity tests pin it.
    """
    P, depth = routes.shape[1], embs.shape[1]
    assert 1 <= k <= P * depth, "k must be in [1, nprobe * depth]"
    if use_pallas is None:
        use_pallas = use_pallas_default()
    use_pallas = use_pallas and not on_tpu()
    # trace-time only (this wrapper runs Python once per jit trace):
    # counts (re)compilations per dispatch path, free at execution time
    obs.count_kernel_trace("rerank", "pallas" if use_pallas else "ref")
    if use_pallas:
        from repro.kernels.rerank.rerank import rerank_topk_pallas

        return rerank_topk_pallas(q, embs, live, routes, k, scales)
    return rerank_topk_ref(q, embs, live, routes, k, scales)
