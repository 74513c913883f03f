"""Pallas TPU kernel: fused MIPS scoring + per-block top-k.

Retrieval hot path of the streaming index (and of the recsys
``retrieval_cand`` cell: 1 query x 1M candidates). Two-phase design adapted
to the TPU memory hierarchy:

  phase 1 (this kernel)   — grid (Q/bq, N/bn); each step computes the
      [bq, bn] fp32 score tile on the MXU and reduces it **in VMEM** to the
      tile's local top-k via k iterations of (row-max, mask). Only
      [bq, k] winners per tile are written back, into a [N/bn, Q, k]
      output whose per-step block (bq, k) spans the array's full last
      dim — the TPU's block-shape rule for a k that is not a multiple of
      128 — the [Q, N] score matrix
      never reaches HBM (a 1M-candidate fp32 score row is 4 MB/query; at
      serve_bulk batch 262k that matrix would be 1 TB).
  phase 2 (ops wrapper)   — jax.lax.top_k over the (N/bn)*k surviving
      candidates per query (tiny), then id re-mapping.

Invalid index rows are masked via an additive bias row (-inf), fused into
the score tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import NEG_INF, interpret_mode, pad_dim


def _mips_kernel(q_ref, x_ref, bias_ref, sc_ref, id_ref, *, bn: int, k: int):
    nb = pl.program_id(1)

    q = q_ref[...].astype(jnp.float32)  # [bq, d]
    x = x_ref[...].astype(jnp.float32)  # [bn, d]
    bias = bias_ref[...].astype(jnp.float32)  # [1, bn]

    s = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + bias  # [bq, bn]

    ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + nb * bn

    # k iterations of (max, mask) extract the tile-local top-k in VMEM.
    for j in range(k):
        m = jnp.max(s, axis=1)  # [bq]
        a = jnp.min(jnp.where(s >= m[:, None], ids, jnp.int32(2**31 - 1)), axis=1)
        sc_ref[:, j] = m
        id_ref[:, j] = a
        s = jnp.where(ids == a[:, None], NEG_INF, s)


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn"))
def mips_topk_pallas(
    q: jnp.ndarray,
    index: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    *,
    bq: int = 128,
    bn: int = 1024,
):
    """See ``ref.mips_topk_ref``."""
    Q, d = q.shape
    N = index.shape[0]
    bq = min(bq, max(8, Q))
    bn = min(bn, max(128, N))

    qp = pad_dim(q, 0, bq)
    xp = pad_dim(index, 0, bn)
    Np = xp.shape[0]
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.pad(bias, (0, Np - N), constant_values=NEG_INF)[None, :]  # [1, Np]

    Qp = qp.shape[0]
    nblocks = Np // bn

    kernel = functools.partial(_mips_kernel, bn=bn, k=k)
    sc, ids = pl.pallas_call(
        kernel,
        grid=(Qp // bq, nblocks),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, n: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, n: (n, 0)),
            pl.BlockSpec((1, bn), lambda i, n: (0, n)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, k), lambda i, n: (n, i, 0)),
            pl.BlockSpec((None, bq, k), lambda i, n: (n, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, Qp, k), jnp.int32),
        ],
        interpret=interpret_mode(),
    )(qp, xp, bias)

    # Phase 2: merge tile winners (nblocks*k candidates/query — tiny), laid
    # out block-major per query so lax.top_k's lowest-index tie-break
    # prefers the lower block, as a single top-k over all N would.
    sc = sc.transpose(1, 0, 2).reshape(Qp, nblocks * k)
    ids = ids.transpose(1, 0, 2).reshape(Qp, nblocks * k)
    top_sc, pos = jax.lax.top_k(sc[:Q], k)
    top_id = jnp.take_along_axis(ids[:Q], pos, axis=1)
    return top_sc, top_id.astype(jnp.int32)
