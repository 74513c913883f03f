"""The main-path Pallas kernels compile for a TPU v5e at real widths.

The TPU compiler is installed with JAX, so each kernel is lowered and
compiled for a *described* v5e chip (``topologies.get_topology_desc``) —
no chip needed. Mosaic refuses here what interpret mode accepts (lane- or
sublane-unaligned slices, illegal block shapes), so these tests guard
every later change at no chip time. Shapes are the 150 MB / d=384
deployment's (``budget_to_config(150, 384, ...)``): K and index capacity
4306 (fp32 rings, depth 16) and 4218 (int8 rings, depth 64), Q = 32,
nprobe = 8, k = 10. Each test asserts the compiled program holds the
Mosaic kernel (``tpu_custom_call``).
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D, Q, NPROBE, K = 384, 32, 8, 10
CONFIGS = {"fp32": (4306, 16, jnp.float32), "int8": (4218, 64, jnp.int8)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off for this module
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_module(name, monkeypatch):
    """The kernel module with interpret mode off, as on a TPU backend."""
    mod = importlib.import_module(f"repro.kernels.{name}.{name}")
    monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    return mod


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_admit_compiles_for_v5e(store_dtype, one_chip, monkeypatch):
    mod = _kernel_module("admit", monkeypatch)
    n_clusters, _, _ = CONFIGS[store_dtype]
    B = 512

    def step(x, basis, centroids, live):
        return mod.admit_pallas(x, basis, centroids, 0.2, live,
                                store_dtype=store_dtype, normalize=True,
                                emit_rows=True)

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((B, D), jnp.float32), ((5, D), jnp.float32),
        ((n_clusters, D), jnp.float32), ((B,), jnp.bool_))]
    assert "tpu_custom_call" in _compiled_text(step, args)


@pytest.mark.parametrize("store_dtype", ["fp32", "int8"])
def test_serve_compiles_for_v5e(store_dtype, one_chip, monkeypatch):
    mod = _kernel_module("serve", monkeypatch)
    n_clusters, depth, emb_dtype = CONFIGS[store_dtype]
    cap = n_clusters
    quantized = store_dtype == "int8"

    def step(qr, qn, vectors, valid, labels, embs, live, *scales):
        return mod.serve_topk_pallas(qr, qn, vectors, valid, labels, embs,
                                     live, K, NPROBE, *scales)

    shapes = [((Q, D), jnp.float32), ((Q, D), jnp.float32),
              ((cap, D), jnp.float32), ((cap,), jnp.bool_),
              ((cap,), jnp.int32), ((n_clusters, depth, D), emb_dtype),
              ((n_clusters, depth), jnp.bool_)]
    if quantized:
        shapes.append(((n_clusters, depth), jnp.float32))
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    assert "tpu_custom_call" in _compiled_text(step, args)


@pytest.mark.parametrize("k", [NPROBE, K])
def test_mips_compiles_for_v5e(k, one_chip, monkeypatch):
    mod = _kernel_module("mips", monkeypatch)
    cap = CONFIGS["int8"][0]   # spans several bn=1024 index blocks

    def step(q, index, valid):
        return mod.mips_topk_pallas(q, index, valid, k)

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((Q, D), jnp.float32), ((cap, D), jnp.float32),
        ((cap,), jnp.bool_))]
    assert "tpu_custom_call" in _compiled_text(step, args)
