"""End-to-end behaviour tests for the paper's system: freshness under
drift, screening efficacy, bounded state under load, serving loop."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.streaming_rag import paper_pipeline_config
from repro.core import heavy_hitter, pipeline
from repro.data.qa import FactStream, exact_match
from repro.data.streams import make_stream
from repro.serve.runtime import AsyncServer, ServerConfig

DIM = 48


def _build(alpha=0.1, **kw):
    cfg = paper_pipeline_config(dim=DIM, k=64, capacity=32,
                                update_interval=128, alpha=alpha, **kw)
    stream = make_stream("twitter", dim=DIM)
    warm = np.concatenate(
        [stream.next_batch(128)["embedding"] for _ in range(2)])
    state = pipeline.init(cfg, jax.random.key(0), jnp.asarray(warm))
    return cfg, state, stream


def test_screening_drops_background_noise():
    cfg, state, stream = _build()
    kept_on, kept_bg = 0, 0
    n_on, n_bg = 0, 0
    for _ in range(8):
        b = stream.next_batch(128)
        state, info = pipeline.ingest_batch(
            cfg, state, jnp.asarray(b["embedding"]), jnp.asarray(b["doc_id"]))
        keep = np.asarray(info["keep"])
        on = b["topic"] >= 0
        kept_on += keep[on].sum()
        n_on += on.sum()
        kept_bg += keep[~on].sum()
        n_bg += max((~on).sum(), 1)
    assert kept_on / n_on > 2.5 * (kept_bg / n_bg)  # screening separates


def test_state_stays_bounded_under_load():
    cfg, state, stream = _build()
    def nbytes(tree):
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree)
                   if hasattr(l, "size") and hasattr(l.dtype, "itemsize"))

    size0 = nbytes(state)
    for _ in range(12):
        b = stream.next_batch(256)
        state, _ = pipeline.ingest_batch(
            cfg, state, jnp.asarray(b["embedding"]), jnp.asarray(b["doc_id"]))
    size1 = nbytes(state)
    assert size0 == size1  # memory budget: state size is shape-static
    assert int(jnp.sum(heavy_hitter.active_mask(state.hh))) <= cfg.hh.capacity


def test_index_freshness_beats_static_snapshot():
    """Fact values drift; streaming index must answer newer values than a
    frozen snapshot (paper case study)."""
    from repro.core import baselines as B

    fs = FactStream(make_stream("btc", dim=DIM), n_entities=24, seed=0)
    cfg = paper_pipeline_config(dim=DIM, k=64, capacity=48,
                                update_interval=64, alpha=0.0)
    warm = fs.next_batch(128)
    state = pipeline.init(cfg, jax.random.key(0),
                          jnp.asarray(warm["embedding"]))
    static = B.make_static_rag(DIM, capacity=128)
    s_state = static.init(jax.random.key(1))
    s_state = static.ingest(s_state, jnp.asarray(warm["embedding"]),
                            jnp.asarray(warm["doc_id"]))
    for _ in range(20):
        b = fs.next_batch(128)
        state, _ = pipeline.ingest_batch(
            cfg, state, jnp.asarray(b["embedding"]), jnp.asarray(b["doc_id"]))

    qs = fs.qa_queries(20)
    em_stream, em_static = [], []
    for q in qs:
        _, _, ids, _ = pipeline.query(cfg, state,
                                      jnp.asarray(q["embedding"])[None], 10)
        em_stream.append(exact_match(fs.read(q, np.asarray(ids)),
                                     q["answer"]))
        out = static.query(s_state, jnp.asarray(q["embedding"])[None], 10)
        em_static.append(exact_match(fs.read(q, np.asarray(out[2])),
                                     q["answer"]))
    assert np.mean(em_stream) >= np.mean(em_static)
    assert np.mean(em_stream) > 0  # retrieves at least some current facts


def test_server_answers_while_ingesting():
    cfg, state, stream = _build()
    server = AsyncServer(cfg, ServerConfig(max_batch=8, max_wait_ms=0.0),
                         jax.random.key(0))
    answered = []
    for i in range(6):
        b = stream.next_batch(64)
        for q in stream.queries(4)["embedding"]:
            server.submit(q)
        answered += server.serve_round(b)
        server.sync()   # the next round's queries see this round's docs
    answered += server.flush()
    server.close()
    assert len(answered) == 24
    assert server.stats["docs"] == 6 * 64
    for a in answered:
        assert a["scores"].shape == (10,)
        assert np.isfinite(a["scores"]).all()


def test_counter_state_change_optimality_accounting():
    """Writes stay near the heavy-hitter lower bound (Jayaram et al.)."""
    from repro.core import theory

    cfg, state, stream = _build()
    for _ in range(10):
        b = stream.next_batch(128)
        state, _ = pipeline.ingest_batch(
            cfg, state, jnp.asarray(b["embedding"]), jnp.asarray(b["doc_id"]))
    w, lb, ratio = theory.state_change_rate(
        state.hh.total_writes, state.hh.total_seen)
    assert float(w) <= float(state.hh.total_seen)
    assert float(ratio) < 50  # within polylog-ish factor of Omega(sqrt(n))
