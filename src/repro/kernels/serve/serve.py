"""Pallas TPU kernel: the fused serve path — route -> gather -> dequant-
rerank -> top-k in ONE device program.

The two-stage query used to run as separate device programs: ``mips``
scored the prototype index and materialized routes in HBM, then the
``rerank`` kernel was launched with the route table as a scalar-prefetch
operand so BlockSpec index maps could drive the ring-tile DMAs. This
kernel collapses both stages: the query block is streamed from HBM once,
prototype route scores are computed on the MXU into a VMEM scratch (the
[Q, cap] score matrix never reaches HBM), the running top-``nprobe``
extraction and the slot -> cluster route-label mapping happen in
registers, and the routed ring tiles are then pulled in by explicit
``pltpu.make_async_copy`` DMAs *driven by the in-kernel route values* —
routes computed inside a kernel cannot feed a BlockSpec index map, which
is exactly why the staged split existed. Serve-side HBM traffic is one
pass over the routed ring tiles (+ one slot row each) plus the query
block and the (tiny, VMEM-resident) prototype index.

int8 ring tiles ride the same DMA path as fp32: the tile and its slot
row of per-slot scales are copied into VMEM, the tile is widened to fp32
*inside the kernel*, scored on the MXU with fp32 accumulation, and the
per-slot scale applied to the score row ((q·e)·s == q·(s·e)) — no fp32
candidate tensor ever exists in HBM. The slot row also carries liveness:
an additive 0 / NEG_INF bias for fp32 rings, a NaN scale for dead int8
slots. It is lane-padded to a whole (1, 128k) tile, the only per-cluster
slice Mosaic lets a DMA take. When the ring depth misses the
dtype's sublane multiple (8 fp32 / 32 int8) only the VMEM staging tile is
padded — the pad rows are zeroed in-kernel and never DMAd, so the store
is NOT copied host-side and the padded rows cost zero HBM bytes (the
staged rerank kernel pads the store itself in that case).

Candidate scores live in a [nprobe, bq, dp] scratch: each (query, probe)
score row is stored as a whole [1, dp] row of its probe's plane. A ring
depth below 128 lanes is thus never a lane slice of a wider row, which
Mosaic refuses (lane slices must be 128-aligned).

Grid: (Q // bq,). Per step: [bq, d] query block; route scores in
``bk``-column chunks of the VMEM-resident [cap, d] index; nprobe
iterations of (row-max, min-id mask) — identical tie-breaking to
``lax.top_k`` — yield routes; per (query, probe) the ring tile is DMAd in
``bd``-row chunks and scored; the final top-k extraction runs k
iterations of (max, min-id) across the nprobe [bq, dp] candidate-score
planes in VMEM, emitting exactly the staged composition's
(scores, pos, routes) with the same dead -> -1 semantics. (bq, bk, bd)
are the autotuner's tile space (``kernels.tuning``).

VMEM working set per step: bq*d (queries, x2) + cap*d (index) + bq*cap
(route scores) + nprobe*bq*dp (candidate scores) + dp*d (tile staging)
fp32 words + the tiny bias/scale rows. Paper defaults (bq=8, cap<=256,
d=384, nprobe=8, depth=16) stay under ~1 MB of the ~16 MB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LANE, NEG_INF, SUBLANE_F32, SUBLANE_I8,
                                  interpret_mode, pad_dim, round_up)

_SENTINEL = 2**31 - 2  # padded-slot id: loses every min-id tie


def _serve_kernel(qr_ref, qn_ref, idx_ref, ibias_ref, lbl_ref,
                  embs_hbm, slot_hbm, sc_ref, pos_ref, rt_ref, rs_scr,
                  cd_scr, e_scr, r_scr, sem, *,
                  capp: int, C: int, depth: int, dp: int, P: int, k: int,
                  bq: int, bk: int, bd: int, quantized: bool):
    # ---- stage 1: prototype route scores, bk columns at a time, into the
    # VMEM scratch — the [Q, cap] score matrix never reaches HBM.
    qr = qr_ref[...].astype(jnp.float32)   # [bq, d]
    qn = qn_ref[...].astype(jnp.float32)   # [bq, d]
    for nb in range(capp // bk):
        s1 = jax.lax.dot_general(
            qr, idx_ref[nb * bk:(nb + 1) * bk, :],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        rs_scr[:, nb * bk:(nb + 1) * bk] = \
            s1 + ibias_ref[:, nb * bk:(nb + 1) * bk]

    # ---- running top-nprobe + route-label mapping, in registers. nprobe
    # iterations of (row-max, min-slot-id) — the same extraction as the
    # mips kernel, so slot order (and its lowest-index tie-break) matches
    # lax.top_k bit-for-bit. The slot -> cluster label lookup is a
    # vectorized select-sum against the VMEM-resident label row.
    rs = rs_scr[...]
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, rs.shape, 1)
    lbl_row = lbl_ref[...]                 # [1, capp] i32 (-1 = dead slot)
    route_cols = []
    for _ in range(P):
        m = jnp.max(rs, axis=1)
        a = jnp.min(jnp.where(rs >= m[:, None], slot_ids,
                              jnp.int32(2**31 - 1)), axis=1)
        lbl = jnp.sum(jnp.where(slot_ids == a[:, None], lbl_row, 0), axis=1)
        route_cols.append(jnp.where((m > NEG_INF / 2) & (lbl >= 0), lbl, -1))
        rs = jnp.where(slot_ids == a[:, None], NEG_INF, rs)
    routes = jnp.stack(route_cols, axis=1).astype(jnp.int32)  # [bq, P]
    rt_ref[...] = routes

    # ---- stage 2: DMA each routed ring tile into VMEM and score it. The
    # sublane pad rows of the staging tile (dp > depth) are zeroed once per
    # step (the whole tile: a sub-tile row slice is not a legal store) and
    # never DMAd: zero rows score 0, then the NEG_INF bias pad kills them
    # — same additive-bias masking as the rerank kernel.
    if dp > depth:
        e_scr[...] = jnp.zeros(e_scr.shape, e_scr.dtype)
    for i in range(bq):
        qi = qn[i:i + 1, :]                # [1, d]
        for j in range(P):
            r = routes[i, j]               # scalar; drives the DMA index
            c = jnp.clip(r, 0, C - 1)
            for t in range(depth // bd):   # bd-row DMA chunks
                cp = pltpu.make_async_copy(
                    embs_hbm.at[c, pl.ds(t * bd, bd)],
                    e_scr.at[pl.ds(t * bd, bd)], sem)
                cp.start()
                cp.wait()
            cpr = pltpu.make_async_copy(slot_hbm.at[c], r_scr, sem)
            cpr.start()
            cpr.wait()
            # int8 tiles widen to fp32 HERE, in VMEM — fp32 MXU accumulate
            e = e_scr[...].astype(jnp.float32)       # [dp, d]
            s = jax.lax.dot_general(
                qi, e, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [1, dp]
            row = r_scr[...][:, :dp]       # this cluster's slot row
            if quantized:                  # dequant scale, NaN = dead slot
                s = s * row
                s = jnp.where(s == s, s, NEG_INF)
            else:
                s = s + row                # live mask as additive bias
            s = jnp.where(r < 0, NEG_INF, s)  # whole tile dead if no route
            cd_scr[j, i:i + 1, :] = s      # whole row of probe j's plane

    # ---- final top-k over the nprobe [bq, dp] candidate planes: k
    # iterations of (max, min-id) with ids = j*depth + slot (pads get a
    # sentinel), == lax.top_k over the staged [Q, P*depth] score table,
    # tie-break included.
    planes = [cd_scr[j] for j in range(P)]
    local = jax.lax.broadcasted_iota(jnp.int32, (bq, dp), 1)
    ids = [jnp.where(local < depth, j * depth + local, jnp.int32(_SENTINEL))
           for j in range(P)]
    for t in range(k):
        m = functools.reduce(jnp.maximum,
                             [jnp.max(p, axis=1) for p in planes])
        a = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(p >= m[:, None], pid, jnp.int32(2**31 - 1)),
                    axis=1) for p, pid in zip(planes, ids)])
        sc_ref[:, t] = m
        pos_ref[:, t] = a
        planes = [jnp.where(pid == a[:, None], NEG_INF, p)
                  for p, pid in zip(planes, ids)]


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "bq", "bk", "bd"))
def serve_topk_pallas(
    qr: jnp.ndarray,
    qn: jnp.ndarray,
    vectors: jnp.ndarray,
    valid: jnp.ndarray,
    route_labels: jnp.ndarray,
    embs: jnp.ndarray,
    live: jnp.ndarray,
    k: int,
    nprobe: int,
    scales: jnp.ndarray | None = None,
    *,
    bq: int = 8,
    bk: int = 128,
    bd: int = 0,
):
    """See ``ref.serve_topk_ref``. (bq, bk, bd) are the autotuned tiles:
    queries per grid step, route-score columns per MXU chunk, and ring
    rows per DMA chunk (0 = whole tile in one copy)."""
    Q, d = qr.shape
    cap = vectors.shape[0]
    C, depth, _ = embs.shape
    quantized = embs.dtype == jnp.int8
    assert (scales is not None) == quantized, \
        "int8 ring buffers require per-slot scales (and fp32 forbids them)"
    sublane = SUBLANE_I8 if quantized else SUBLANE_F32
    dp = round_up(max(depth, 1), sublane)
    dl = round_up(dp, LANE)

    bq = round_up(min(bq, max(1, Q)), SUBLANE_F32)
    bk = min(bk, round_up(max(cap, 1), LANE))
    bd = bd if 0 < bd <= depth and depth % bd == 0 else depth

    qrp = pad_dim(qr.astype(jnp.float32), 0, bq)
    qnp_ = pad_dim(qn.astype(jnp.float32), 0, bq)
    Qp = qrp.shape[0]
    vp = pad_dim(vectors.astype(jnp.float32), 0, bk)
    capp = vp.shape[0]
    ibias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    ibias = jnp.pad(ibias, (0, capp - cap),
                    constant_values=NEG_INF)[None, :]          # [1, capp]
    lblp = jnp.pad(route_labels.astype(jnp.int32), (0, capp - cap),
                   constant_values=-1)[None, :]                # [1, capp]
    # One per-slot row per cluster carries liveness: an additive bias
    # (0 live, NEG_INF dead or pad) for fp32 rings, the dequant scale
    # (NaN dead or pad) for int8 rings — one DMA per routed cluster. The
    # rows are lane-padded to [C, 1, dl], so one cluster's row is a whole
    # (1, 128k) tile, the only DMA source slice Mosaic accepts. The store
    # itself is never padded or copied (only its VMEM staging tile).
    if quantized:
        slot_rows = jnp.where(live, scales.astype(jnp.float32), jnp.nan)
    else:
        slot_rows = jnp.where(live, 0.0, NEG_INF).astype(jnp.float32)
    slot_rows = pad_dim(slot_rows, 1, LANE,
                        value=jnp.nan if quantized else NEG_INF)[:, None]
    operands = [qrp, qnp_, vp, ibias, lblp, embs, slot_rows]
    in_specs = [
        pl.BlockSpec((bq, d), lambda i: (i, 0)),
        pl.BlockSpec((bq, d), lambda i: (i, 0)),
        pl.BlockSpec((capp, d), lambda i: (0, 0)),
        pl.BlockSpec((1, capp), lambda i: (0, 0)),
        pl.BlockSpec((1, capp), lambda i: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),   # ring tiles: manual DMA
        pl.BlockSpec(memory_space=pl.ANY),   # slot rows: manual DMA
    ]
    scratch = [
        pltpu.VMEM((bq, capp), jnp.float32),       # route scores
        pltpu.VMEM((nprobe, bq, dp), jnp.float32),  # candidate scores
        pltpu.VMEM((dp, d), embs.dtype),           # ring-tile staging
        pltpu.VMEM((1, dl), jnp.float32),          # slot row staging
        pltpu.SemaphoreType.DMA,
    ]

    kernel = functools.partial(
        _serve_kernel, capp=capp, C=C, depth=depth, dp=dp, P=nprobe, k=k,
        bq=bq, bk=bk, bd=bd, quantized=quantized)
    sc, pos, routes = pl.pallas_call(
        kernel,
        grid=(Qp // bq,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
            pl.BlockSpec((bq, nprobe), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
            jax.ShapeDtypeStruct((Qp, nprobe), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret_mode(),
    )(*operands)

    sc, pos, routes = sc[:Q], pos[:Q], routes[:Q]
    pos = jnp.where((sc > NEG_INF / 2) & (pos < nprobe * depth), pos, -1)
    return sc, pos.astype(jnp.int32), routes


def modeled_dma_bytes(Q: int, d: int, cap: int, C: int, depth: int,
                      nprobe: int, k: int, quantized: bool) -> int:
    """Exact serve-side HBM traffic of one fused-kernel call: everything
    the program streams (query blocks, the VMEM-resident index + its
    valid/label rows, the per-(query, probe) ring-tile and slot-row DMAs)
    plus its outputs. This is the DMA ledger of the kernel above — kept
    analytic because interpret-mode HLO does not model the TPU DMA
    pattern — and the number ``kernel_bench``/table19 check against the
    roofline ideal of one pass over the routed rings + the query block.
    """
    itemsize = 1 if quantized else 4
    q_bytes = 2 * Q * d * 4                       # qr + qn blocks
    index_bytes = cap * d * 4 + 2 * cap * 4       # vectors + ibias + labels
    # ring tile + its lane-padded slot row (bias, or scale for int8)
    tile = depth * d * itemsize + round_up(depth, LANE) * 4
    out_bytes = Q * k * 8 + Q * nprobe * 4
    return q_bytes + index_bytes + Q * nprobe * tile + out_bytes


def ideal_serve_bytes(Q: int, d: int, depth: int, nprobe: int,
                      quantized: bool) -> int:
    """The roofline lower bound the ROADMAP states the target against:
    ONE pass over the routed ring tiles plus the query block."""
    itemsize = 1 if quantized else 4
    return Q * nprobe * depth * d * itemsize + Q * d * 4
