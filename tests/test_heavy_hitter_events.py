"""The heavy-hitter event loop (``update_batch_events``) against the
per-arrival scan (``update_batch_scan``): every ``HHState`` leaf and every
per-arrival ``info`` array bit-identical, over successive batches, for the
configurations ``update_batch`` runs as an event loop; the engine's ingest
step with either; and the label-change rate derived from the counters."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import heavy_hitter as hh
from repro.core import clustering, pipeline, prefilter
from repro.data.streams import make_stream
from repro.engine import stages
from repro.engine.engine import ingest_impl

_scan = jax.jit(hh.update_batch_scan, static_argnums=0)
_events = jax.jit(hh.update_batch_events, static_argnums=0)


def _assert_same(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if jnp.issubdtype(la.dtype, jax.dtypes.prng_key):
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _full_state(cfg, rng, labels=None):
    labels = np.arange(cfg.capacity) if labels is None else labels
    return hh.init(cfg)._replace(
        labels=jnp.asarray(labels, jnp.int32),
        counts=jnp.asarray(rng.integers(1, 6, cfg.capacity), jnp.int32))


def _case(name, rng):
    """(cfg, incoming state, [batches of labels]) for one parity case."""
    if name == "fill_below_capacity":
        cfg = hh.HHConfig(capacity=64, admit_prob=0.05)
        return cfg, hh.init(cfg), [rng.integers(0, 40, 96) for _ in range(3)]
    if name in ("full_evict_u005", "full_evict_u1"):
        u = 0.05 if name == "full_evict_u005" else 1.0
        cfg = hh.HHConfig(capacity=16, admit_prob=u)
        return cfg, _full_state(cfg, rng), [rng.integers(0, 60, 128)
                                            for _ in range(4)]
    if name == "all_hit":
        cfg = hh.HHConfig(capacity=16, admit_prob=0.05)
        return cfg, _full_state(cfg, rng), [rng.integers(0, 16, 200)
                                            for _ in range(2)]
    if name == "all_invalid":
        cfg = hh.HHConfig(capacity=16, admit_prob=1.0)
        return cfg, _full_state(cfg, rng), [np.full(64, -1)]
    if name == "dead_rows_mixed":
        cfg = hh.HHConfig(capacity=12, admit_prob=0.3)
        return cfg, hh.init(cfg), [
            np.where(rng.random(100) < 0.4, -1, rng.integers(0, 30, 100))
            for _ in range(4)]
    if name == "space_saving":
        cfg = hh.HHConfig(capacity=10, policy=hh.Policy.SPACE_SAVING)
        return cfg, hh.init(cfg), [rng.zipf(1.5, 150) % 40 for _ in range(3)]
    if name == "gate_below_capacity":
        cfg = hh.HHConfig(capacity=20, admit_prob=0.3,
                          gate_below_capacity=True)
        return cfg, hh.init(cfg), [rng.integers(0, 50, 80) for _ in range(3)]
    if name == "hotset_all_miss":
        cfg = hh.HHConfig(capacity=32, admit_prob=1.0)
        return cfg, _full_state(cfg, rng), [1000 + 32 * i + np.arange(32)
                                            for i in range(3)]
    if name in ("paper_b50", "paper_b2048"):
        b = 50 if name == "paper_b50" else 2048
        cfg = hh.HHConfig(capacity=4218, admit_prob=0.05)
        return cfg, hh.init(cfg), [rng.integers(-1, 4218, b) for _ in range(3)]
    raise KeyError(name)


CASES = ["fill_below_capacity", "full_evict_u005", "full_evict_u1",
         "all_hit", "all_invalid", "dead_rows_mixed", "space_saving",
         "gate_below_capacity", "hotset_all_miss", "paper_b50", "paper_b2048"]


@pytest.mark.parametrize("name", CASES)
def test_events_match_scan_bitwise(name):
    rng = np.random.default_rng(CASES.index(name))
    cfg, state, batches = _case(name, rng)
    assert hh.events_exact(cfg)
    s_scan = s_ev = state
    for i, labels in enumerate(batches):
        labels = jnp.asarray(labels, jnp.int32)
        key = jax.random.key(100 + i)
        s_scan, info_scan = _scan(cfg, s_scan, labels, key)
        s_ev, info_ev = _events(cfg, s_ev, labels, key)
        _assert_same(s_scan, s_ev)
        assert set(info_scan) == set(info_ev)
        for k in info_scan:
            np.testing.assert_array_equal(np.asarray(info_scan[k]),
                                          np.asarray(info_ev[k]), err_msg=k)


@pytest.mark.parametrize("cfg,exact", [
    (hh.HHConfig(policy=hh.Policy.MIN_EVICT), True),
    (hh.HHConfig(policy=hh.Policy.SPACE_SAVING), True),
    (hh.HHConfig(policy=hh.Policy.RANDOM_EVICT), False),
    (hh.HHConfig(policy=hh.Policy.COUNT_MIN), False),
    (hh.HHConfig(morris=True), False),
    (hh.HHConfig(adaptive=True, max_capacity=200), False),
])
def test_update_batch_picks_path_from_config(cfg, exact):
    """update_batch is the event loop exactly where events_exact holds,
    and the scan elsewhere: its results equal the chosen path's."""
    assert hh.events_exact(cfg) is exact
    rng = np.random.default_rng(7)
    labels = jnp.asarray(rng.integers(0, 300, 256), jnp.int32)
    key = jax.random.key(3)
    got = hh.update_batch(cfg, hh.init(cfg), labels, key)
    want = (_events if exact else _scan)(cfg, hh.init(cfg), labels, key)
    _assert_same(got, want)


DIM = 32


def _engine_cfg(capacity):
    return pipeline.PipelineConfig(
        pre=prefilter.PrefilterConfig(num_vectors=3, dim=DIM, alpha=0.0,
                                      basis="fixed"),
        clus=clustering.ClusterConfig(num_clusters=16, dim=DIM),
        hh=hh.HHConfig(capacity=capacity, admit_prob=0.05),
        update_interval=64, store_depth=4)


@pytest.mark.parametrize("capacity", [16, 6])
def test_ingest_step_same_with_scan_forced(capacity, monkeypatch):
    """The engine's ingest step gives the same state and info with the
    event loop as with the counter's scan forced in its place (capacity
    equal to the cluster count, as the deployments run, and below it)."""
    cfg = _engine_cfg(capacity)
    s = make_stream("iot", dim=DIM)
    batches = []
    for i in range(6):
        b = s.next_batch(40)
        ids = np.asarray(b["doc_id"]).copy()
        if i % 2:
            ids[::5] = -1                  # dead rows
        batches.append((jnp.asarray(b["embedding"]), jnp.asarray(ids)))

    def run():
        step = jax.jit(ingest_impl, static_argnums=0)
        state, infos = pipeline.init(cfg, jax.random.key(0)), []
        for x, ids in batches:
            state, info = step(cfg, state, x, ids)
            infos.append(info)
        return state, infos

    events = run()
    monkeypatch.setattr(hh, "update_batch",
                        jax.jit(hh.update_batch_scan, static_argnums=0))
    scan = run()
    _assert_same(events, scan)


def test_label_change_rate_falls_once_counter_fills():
    """hh_label_change_rate = (evictions + occupied) / seen: on a stream
    whose counter fills (capacity = cluster count) and then only hits, it
    falls toward capacity / arrivals."""
    cfg = _engine_cfg(16)
    s = make_stream("iot", dim=DIM)
    state = pipeline.init(cfg, jax.random.key(0))
    rates, occupied = [], []
    for _ in range(8):
        b = s.next_batch(64)
        state, _ = pipeline.ingest_batch(cfg, state,
                                         jnp.asarray(b["embedding"]),
                                         jnp.asarray(b["doc_id"]))
        c = stages.decode_pipeline_counters(
            np.asarray(stages.pipeline_counters(cfg, state))[None])
        assert c["hh_evictions"] == 0
        assert c["hh_label_change_rate"] == pytest.approx(
            c["hh_occupied"] / c["hh_seen"])
        rates.append(c["hh_label_change_rate"])
        occupied.append(c["hh_occupied"])
    # every cluster that receives kept docs is tracked: only hits remain
    assert occupied[-1] == occupied[-3] <= cfg.hh.capacity
    assert rates[-1] < rates[-2] < rates[-3] < rates[0]
    assert rates[-1] <= cfg.hh.capacity / c["hh_seen"] + 1e-12


def test_label_change_rate_counts_evictions():
    vec = np.zeros(len(stages.PIPELINE_COUNTER_NAMES), np.int64)
    names = stages.PIPELINE_COUNTER_NAMES
    vec[names.index("hh_seen")] = 200
    vec[names.index("hh_evictions")] = 30
    vec[names.index("hh_occupied")] = 10
    c = stages.decode_pipeline_counters(vec[None])
    assert c["hh_label_change_rate"] == pytest.approx(40 / 200)
    assert stages.decode_pipeline_counters(
        np.zeros_like(vec)[None])["hh_label_change_rate"] == 0.0
