"""jit'd public wrapper for top-k MIPS retrieval."""
from __future__ import annotations

import jax.numpy as jnp

from repro import obs
from repro.kernels.common import use_pallas_default
from repro.kernels.mips.ref import mips_topk_ref


def mips_topk(
    q: jnp.ndarray,
    index: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    *,
    use_pallas: bool | None = None,
):
    """Top-k inner-product search: (scores [Q,k] f32 desc, ids [Q,k] i32).

    ``valid`` rows of the index are retrievable; invalid rows never surface.
    For cosine retrieval, pre-normalize q and index (the streaming index
    stores normalized prototypes).
    """
    assert k >= 1 and k <= index.shape[0], "k must be in [1, N]"
    if use_pallas is None:
        use_pallas = use_pallas_default()
    # trace-time only: counts (re)compilations per dispatch path
    obs.count_kernel_trace("mips", "pallas" if use_pallas else "ref")
    if use_pallas:
        from repro.kernels.mips.mips import mips_topk_pallas

        return mips_topk_pallas(q, index, valid, k)
    return mips_topk_ref(q, index, valid, k)
