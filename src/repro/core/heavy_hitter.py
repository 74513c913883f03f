"""Counter-based streaming heavy-hitter filter (paper §Streaming Heavy-Hitter
Filtering), as a pure-JAX functional state machine.

TPU adaptation: the paper's Python dict / min-heap becomes two dense vectors
``labels[Bmax]`` (int32, −1 = empty) and ``counts[Bmax]`` — membership, min,
and eviction are O(B) *vector* ops on the VPU, which beats pointer-chasing at
B ≈ 100–1000 and keeps the whole filter jittable inside ``lax.scan``.

Policies (paper Table 8):
  RANDOM_EVICT — Algorithm 1: evict a uniform-random occupied slot.
  MIN_EVICT    — paper default prose: evict the least-frequent label.
  SPACE_SAVING — Metwally-style: replace min, inherit min_count + 1.
  COUNT_MIN    — admit only if a Count-Min sketch estimate of the newcomer
                 exceeds the current minimum count (then evict the min).

Counting modes: exact int32 or Morris approximate counters (store exponent c,
increment w.p. 2^-c, estimate 2^c − 1).

Adaptive u_t / B_t (paper Table 9): when the rate of novel labels inside a
window exceeds ``novel_hi``, grow the admission probability and the active
capacity; decay them back toward defaults when the stream stabilizes.

Per-arrival semantics are preserved exactly. The reference form of a
microbatch is ``update_batch_scan``, a ``lax.scan`` of ``update_one`` over
items. ``update_batch`` runs it for RANDOM_EVICT, COUNT_MIN, Morris and
adaptive counters. For exact, fixed MIN_EVICT and SPACE_SAVING counters it
runs ``update_batch_events`` instead, which gives bit-identical state and
per-arrival info: only the arrivals that change the counter's labels (an
insert or an eviction) are handled one at a time, and the hits and rejected
misses between two of them are applied as one order-free vector step. All
state transitions are pure — checkpointable and mergeable across data
shards (see distributed/collectives.py).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

INT_MAX = jnp.int32(2**31 - 1)
EMPTY = jnp.int32(-1)


class Policy(enum.IntEnum):
    RANDOM_EVICT = 0
    MIN_EVICT = 1
    SPACE_SAVING = 2
    COUNT_MIN = 3


@dataclasses.dataclass(frozen=True)
class HHConfig:
    """Static heavy-hitter configuration (paper Table 2 defaults)."""

    capacity: int = 100              # B
    admit_prob: float = 0.05         # u
    policy: Policy = Policy.MIN_EVICT
    morris: bool = False             # Morris approximate counters
    # Algorithm 1 admits unconditionally below capacity; the paper's update
    # equation gates on U<=u even then. Both are supported (paper ambiguity
    # documented in DESIGN.md §8); default follows Algorithm 1.
    gate_below_capacity: bool = False
    # Count-Min sketch (only used when policy == COUNT_MIN).
    cms_depth: int = 4
    cms_width: int = 256
    # Adaptive u_t / B_t (paper Table 9). Disabled by default.
    adaptive: bool = False
    max_capacity: int | None = None  # B_max when adaptive (>= capacity)
    window: int = 256                # novelty-rate window (arrivals)
    novel_hi: float = 0.5            # grow u_t/B_t above this novelty rate
    novel_lo: float = 0.1            # decay back below this
    u_growth: float = 2.0
    u_max: float = 0.5
    b_step: int = 16

    def __post_init__(self):
        # Fail at config construction, not at the first init()/update():
        # a zero capacity silently produces empty label vectors and a
        # non-positive sketch shape breaks the CMS hashing — both used to
        # surface as shape errors deep inside jit.
        if self.capacity <= 0:
            raise ValueError(
                f"HHConfig.capacity must be positive, got {self.capacity}")
        if self.cms_depth <= 0:
            raise ValueError(
                f"HHConfig.cms_depth must be positive, got {self.cms_depth}")
        if self.cms_width <= 0:
            raise ValueError(
                f"HHConfig.cms_width must be positive, got {self.cms_width}")
        if self.max_capacity is not None and self.max_capacity <= 0:
            raise ValueError(
                "HHConfig.max_capacity must be positive when set, got "
                f"{self.max_capacity}")
        if self.window <= 0:
            raise ValueError(
                f"HHConfig.window must be positive, got {self.window}")

    def bmax(self) -> int:
        if self.adaptive and self.max_capacity is not None:
            return max(self.max_capacity, self.capacity)
        return self.capacity


class HHState(NamedTuple):
    """Dense functional counter state (a pytree; scan/checkpoint friendly)."""

    labels: jnp.ndarray        # [Bmax] int32, EMPTY where unoccupied
    counts: jnp.ndarray        # [Bmax] int32 (Morris: exponent c)
    cms: jnp.ndarray           # [depth, width] int32 Count-Min sketch
    admit_prob: jnp.ndarray    # f32 scalar u_t
    active_capacity: jnp.ndarray  # i32 scalar B_t <= Bmax
    novel_in_window: jnp.ndarray  # i32 scalar
    seen_in_window: jnp.ndarray   # i32 scalar
    total_seen: jnp.ndarray       # i64-ish i32 scalar (stats)
    total_evictions: jnp.ndarray  # i32 scalar (state-change accounting)
    total_writes: jnp.ndarray     # i32 scalar: slot writes (Jayaram state changes)


def init(cfg: HHConfig) -> HHState:
    bmax = cfg.bmax()
    return HHState(
        labels=jnp.full((bmax,), EMPTY, jnp.int32),
        counts=jnp.zeros((bmax,), jnp.int32),
        cms=jnp.zeros((cfg.cms_depth, cfg.cms_width), jnp.int32),
        admit_prob=jnp.float32(cfg.admit_prob),
        active_capacity=jnp.int32(cfg.capacity),
        novel_in_window=jnp.int32(0),
        seen_in_window=jnp.int32(0),
        total_seen=jnp.int32(0),
        total_evictions=jnp.int32(0),
        total_writes=jnp.int32(0),
    )


def estimated_counts(cfg: HHConfig, state: HHState) -> jnp.ndarray:
    """Exact counts, or the Morris estimate 2^c − 1."""
    if cfg.morris:
        return (jnp.exp2(state.counts.astype(jnp.float32)) - 1.0).astype(jnp.float32)
    return state.counts.astype(jnp.float32)


def active_mask(state: HHState) -> jnp.ndarray:
    slot = jnp.arange(state.labels.shape[0], dtype=jnp.int32)
    return (state.labels != EMPTY) & (slot < state.active_capacity)


def _cms_hash(label: jnp.ndarray, depth: int, width: int) -> jnp.ndarray:
    """Universal-ish integer hashing, one row per depth."""
    seeds = jnp.arange(1, depth + 1, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1)
    h = (label.astype(jnp.uint32) + seeds) * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return (h % jnp.uint32(width)).astype(jnp.int32)


def _cms_update_and_estimate(cms: jnp.ndarray, label: jnp.ndarray):
    depth, width = cms.shape
    cols = _cms_hash(label, depth, width)
    rows = jnp.arange(depth, dtype=jnp.int32)
    new_cms = cms.at[rows, cols].add(1)
    est = jnp.min(new_cms[rows, cols])
    return new_cms, est


def update_one(
    cfg: HHConfig, state: HHState, label: jnp.ndarray, key: jax.Array
) -> tuple[HHState, dict]:
    """One arrival. Returns (new_state, info) with
    info = {admitted: bool, evicted_label: int32 (EMPTY if none), slot: int32}.
    ``label`` < 0 means the item was dropped upstream (prefilter) — no-op.
    """
    bmax = state.labels.shape[0]
    slot_ids = jnp.arange(bmax, dtype=jnp.int32)
    ka, kb, kc = jax.random.split(key, 3)

    valid = label >= 0
    occ = active_mask(state)
    hit_vec = occ & (state.labels == label)
    found = jnp.any(hit_vec)
    hit_slot = jnp.argmax(hit_vec).astype(jnp.int32)

    size = jnp.sum(occ.astype(jnp.int32))
    has_room = size < state.active_capacity
    # first empty active slot
    empty_ok = (state.labels == EMPTY) & (slot_ids < state.active_capacity)
    empty_slot = jnp.argmax(empty_ok).astype(jnp.int32)

    u = jax.random.uniform(ka)
    gate = u <= state.admit_prob
    admit_room = jnp.where(jnp.bool_(cfg.gate_below_capacity), gate, True)

    # --- Count-Min sketch bookkeeping (always track when policy needs it) ---
    if cfg.policy == Policy.COUNT_MIN:
        new_cms, cms_est = _cms_update_and_estimate(state.cms, label)
        new_cms = jnp.where(valid, new_cms, state.cms)
    else:
        new_cms, cms_est = state.cms, jnp.int32(0)

    counts_f = jnp.where(occ, state.counts, INT_MAX)  # min over occupied
    min_slot = jnp.argmin(counts_f).astype(jnp.int32)
    min_count = counts_f[min_slot]

    # --- eviction victim per policy ---
    if cfg.policy == Policy.RANDOM_EVICT:
        # uniform over occupied slots via Gumbel-max on the mask
        g = jax.random.gumbel(kb, (bmax,))
        victim = jnp.argmax(jnp.where(occ, g, -jnp.inf)).astype(jnp.int32)
        admit_full = gate
        evict_count = jnp.int32(1)
    elif cfg.policy == Policy.MIN_EVICT:
        victim = min_slot
        admit_full = gate
        evict_count = jnp.int32(1)
    elif cfg.policy == Policy.SPACE_SAVING:
        victim = min_slot
        admit_full = jnp.bool_(True)  # Space-Saving always replaces the min
        # inherit min count (+1); in Morris mode inherit the exponent as-is
        evict_count = min_count if cfg.morris else min_count + 1
    else:  # COUNT_MIN
        victim = min_slot
        admit_full = cms_est >= (min_count + 1)
        evict_count = jnp.int32(1)

    # --- Morris / exact increment on hit ---
    c_hit = state.counts[hit_slot]
    if cfg.morris:
        inc = (jax.random.uniform(kc) < jnp.exp2(-c_hit.astype(jnp.float32)))
        hit_count = c_hit + inc.astype(jnp.int32)
    else:
        hit_count = c_hit + 1

    # --- compose the three transition kinds ---
    do_hit = valid & found
    do_insert = valid & ~found & has_room & admit_room
    do_evict = valid & ~found & ~has_room & admit_full

    slot = jnp.where(do_hit, hit_slot, jnp.where(do_insert, empty_slot, victim))
    write = do_hit | do_insert | do_evict
    new_cnt = jnp.where(
        do_hit, hit_count, jnp.where(do_insert, jnp.int32(1), evict_count)
    ).astype(jnp.int32)

    labels = jnp.where(write, state.labels.at[slot].set(label), state.labels)
    counts = jnp.where(write, state.counts.at[slot].set(new_cnt), state.counts)
    evicted_label = jnp.where(do_evict, state.labels[victim], EMPTY)

    # --- adaptive u_t / B_t ---
    novel = valid & ~found
    seen_w = state.seen_in_window + valid.astype(jnp.int32)
    novel_w = state.novel_in_window + novel.astype(jnp.int32)
    admit_prob = state.admit_prob
    active_capacity = state.active_capacity
    if cfg.adaptive:
        window_done = seen_w >= cfg.window
        rate = novel_w.astype(jnp.float32) / jnp.maximum(seen_w, 1).astype(jnp.float32)
        grow = window_done & (rate > cfg.novel_hi)
        shrink = window_done & (rate < cfg.novel_lo)
        admit_prob = jnp.where(
            grow, jnp.minimum(state.admit_prob * cfg.u_growth, cfg.u_max),
            jnp.where(shrink,
                      jnp.maximum(state.admit_prob / cfg.u_growth, cfg.admit_prob),
                      state.admit_prob))
        active_capacity = jnp.where(
            grow, jnp.minimum(state.active_capacity + cfg.b_step, bmax),
            jnp.where(shrink,
                      jnp.maximum(state.active_capacity - cfg.b_step, cfg.capacity),
                      state.active_capacity)).astype(jnp.int32)
        seen_w = jnp.where(window_done, 0, seen_w)
        novel_w = jnp.where(window_done, 0, novel_w)

    new_state = HHState(
        labels=labels,
        counts=counts,
        cms=new_cms,
        admit_prob=admit_prob,
        active_capacity=active_capacity,
        novel_in_window=novel_w,
        seen_in_window=seen_w,
        total_seen=state.total_seen + valid.astype(jnp.int32),
        total_evictions=state.total_evictions + do_evict.astype(jnp.int32),
        total_writes=state.total_writes + write.astype(jnp.int32),
    )
    info = {
        "admitted": do_insert | do_evict,
        "hit": do_hit,
        "evicted_label": evicted_label,
        "slot": jnp.where(write, slot, jnp.int32(-1)),
    }
    return new_state, info


def update_batch_scan(
    cfg: HHConfig, state: HHState, labels: jnp.ndarray, key: jax.Array
) -> tuple[HHState, dict]:
    """Scan the per-arrival update over a microbatch (paper semantics exact).

    The reference form of ``update_batch``, for every configuration.
    labels: [B] int32 cluster labels, −1 for upstream-dropped items.
    """
    keys = jax.random.split(key, labels.shape[0])

    def step(s, xs):
        lbl, k = xs
        return update_one(cfg, s, lbl, k)

    return jax.lax.scan(step, state, (labels, keys))


def events_exact(cfg: HHConfig) -> bool:
    """True where ``update_batch_events`` reproduces the scan: exact counts,
    a fixed u and capacity, and a victim that is the least-count slot.
    RANDOM_EVICT and COUNT_MIN draw or hash per arrival, Morris increments
    are random and adaptive counters change u and B inside the batch."""
    return (not cfg.morris and not cfg.adaptive
            and cfg.policy in (Policy.MIN_EVICT, Policy.SPACE_SAVING))


def update_batch_events(
    cfg: HHConfig, state: HHState, labels: jnp.ndarray, key: jax.Array
) -> tuple[HHState, dict]:
    """``update_batch_scan`` for ``events_exact`` configurations, looping
    over label-changing arrivals ("events") instead of over every arrival.

    Between two events the slot that holds each label is fixed, so every
    arrival there is either a hit (``counts[slot] += 1``, which commutes) or
    a rejected miss (no change). Each ``while_loop`` step finds the first
    event ``e`` at or after a position ``p``, records the hits of
    ``[p, e)``, runs event ``e`` exactly as ``update_one`` would and goes on
    from ``e + 1``. While the counter has room an event is an insert at the
    first empty slot, which reads no count, so the hits wait and are added
    in one scatter when the counter fills or the batch ends; once it is
    full an event evicts the least-count slot, so each step first adds the
    hits before it. The admission draws are the scan's own: the same
    per-arrival keys, drawn up front. Live labels are distinct (as every
    update keeps them), so an eviction unmaps only its victim slot.
    """
    n = labels.shape[0]
    bmax = state.labels.shape[0]
    cap = state.active_capacity
    slot_ids = jnp.arange(bmax, dtype=jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)

    keys = jax.random.split(key, n)
    u = jax.vmap(lambda k: jax.random.uniform(jax.random.split(k, 3)[0]))(keys)
    gate = u <= state.admit_prob
    valid = labels >= 0
    admit_room = gate if cfg.gate_below_capacity else valid
    admit_full = gate if cfg.policy == Policy.MIN_EVICT else valid

    # the slot holding each arrival's label in the incoming state, or -1
    occ = active_mask(state)
    held = jnp.min(jnp.where(occ[None, :] & (state.labels[None, :]
                                             == labels[:, None]),
                             slot_ids[None, :], bmax), axis=1)
    mslot = jnp.where(held < bmax, held, -1).astype(jnp.int32)

    def add_hits(cnt, hit, slot):
        return cnt.at[jnp.where(hit, slot, bmax)].add(1, mode="drop")

    def step(full: bool):
        def body(c):
            p, size, lab, cnt, mslot, slot_out, adm_out, ev_out, n_evict = c
            event = ((pos >= p) & valid & (mslot < 0)
                     & (admit_full if full else admit_room))
            e = jnp.min(jnp.where(event, pos, n))
            hit = (pos >= p) & (pos < e) & (mslot >= 0)
            slot_out = jnp.where(hit, mslot, slot_out)
            ev = e < n                 # event e; nothing when e == n
            if full:
                cnt = add_hits(cnt, hit, mslot)
                counts_f = jnp.where((lab != EMPTY) & (slot_ids < cap), cnt,
                                     INT_MAX)
                slot = jnp.argmin(counts_f).astype(jnp.int32)
                new_count = (jnp.int32(1) if cfg.policy == Policy.MIN_EVICT
                             else counts_f[slot] + 1)
                evicted = jnp.where(ev, lab[slot], EMPTY)
            else:
                slot = jnp.argmax((lab == EMPTY) & (slot_ids < cap)
                                  ).astype(jnp.int32)
                new_count = jnp.int32(1)
                evicted = EMPTY
            lbl = labels[jnp.minimum(e, n - 1)]
            lab = jnp.where(ev, lab.at[slot].set(lbl), lab)
            cnt = jnp.where(ev, cnt.at[slot].set(new_count), cnt)
            if full:                   # the victim's arrivals now miss
                mslot = jnp.where(ev & (mslot == slot), -1, mslot)
                n_evict = n_evict + ev.astype(jnp.int32)
            else:
                size = size + ev.astype(jnp.int32)
            mslot = jnp.where(ev & (labels == lbl), slot, mslot)
            at_e = ev & (pos == e)
            return (e + 1, size, lab, cnt, mslot,
                    jnp.where(at_e, slot, slot_out), adm_out | at_e,
                    jnp.where(at_e, evicted, ev_out), n_evict)
        return body

    c = (jnp.int32(0), jnp.sum(occ.astype(jnp.int32)), state.labels,
         state.counts, mslot, jnp.full((n,), -1, jnp.int32),
         jnp.zeros((n,), bool), jnp.full((n,), EMPTY, jnp.int32),
         jnp.int32(0))
    c = jax.lax.while_loop(lambda c: (c[0] < n) & (c[1] < cap), step(False), c)
    p, size, lab, cnt, mslot, slot_out, adm_out, ev_out, n_evict = c
    cnt = add_hits(cnt, (slot_out >= 0) & ~adm_out, slot_out)
    c = (p, size, lab, cnt, mslot, slot_out, adm_out, ev_out, n_evict)
    c = jax.lax.while_loop(lambda c: c[0] < n, step(True), c)
    _, _, lab, cnt, _, slot_out, adm_out, ev_out, n_evict = c

    write = slot_out >= 0
    hit = write & ~adm_out
    n_valid = jnp.sum(valid.astype(jnp.int32))
    n_novel = n_valid - jnp.sum(hit.astype(jnp.int32))
    new_state = state._replace(
        labels=lab,
        counts=cnt,
        novel_in_window=state.novel_in_window + n_novel,
        seen_in_window=state.seen_in_window + n_valid,
        total_seen=state.total_seen + n_valid,
        total_evictions=state.total_evictions + n_evict,
        total_writes=state.total_writes + jnp.sum(write.astype(jnp.int32)),
    )
    info = {"admitted": adm_out, "hit": hit, "evicted_label": ev_out,
            "slot": slot_out}
    return new_state, info


@functools.partial(jax.jit, static_argnames=("cfg",))
def update_batch(
    cfg: HHConfig, state: HHState, labels: jnp.ndarray, key: jax.Array
) -> tuple[HHState, dict]:
    """One microbatch of arrivals, with the per-arrival semantics of
    ``update_batch_scan``; ``update_batch_events`` where ``events_exact``.

    labels: [B] int32 cluster labels, −1 for upstream-dropped items.
    """
    if events_exact(cfg):
        return update_batch_events(cfg, state, labels, key)
    return update_batch_scan(cfg, state, labels, key)


def merge(cfg: HHConfig, a: HHState, b: HHState) -> HHState:
    """Merge two shard-local counters into one (distributed consistency).

    Union the label sets with summed (estimated) counts, keep the top-B.
    Used by distributed/collectives.py after an all-gather of shard states.
    """
    labels = jnp.concatenate([a.labels, b.labels])
    counts = jnp.concatenate([estimated_counts(cfg, a), estimated_counts(cfg, b)])
    occ = jnp.concatenate([active_mask(a), active_mask(b)])
    counts = jnp.where(occ, counts, 0.0)
    labels = jnp.where(occ, labels, EMPTY)

    # Sum duplicate labels: sort by label, segment-sum runs.
    order = jnp.argsort(labels)
    sl, sc = labels[order], counts[order]
    first = jnp.concatenate([jnp.array([True]), sl[1:] != sl[:-1]])
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    summed = jax.ops.segment_sum(sc, seg, num_segments=sl.shape[0])
    uniq_label = jnp.where(first, sl, EMPTY)
    uniq_count = jnp.where(first & (sl != EMPTY), summed[seg], 0.0)

    bmax = a.labels.shape[0]
    top_count, top_idx = jax.lax.top_k(uniq_count, bmax)
    top_label = uniq_label[top_idx]
    keep = top_count > 0
    out_counts = jnp.where(keep, top_count, 0.0)
    if cfg.morris:
        out_counts = jnp.ceil(jnp.log2(out_counts + 1.0))
    return HHState(
        labels=jnp.where(keep, top_label, EMPTY).astype(jnp.int32),
        counts=out_counts.astype(jnp.int32),
        cms=a.cms + b.cms,
        admit_prob=jnp.maximum(a.admit_prob, b.admit_prob),
        active_capacity=jnp.maximum(a.active_capacity, b.active_capacity),
        novel_in_window=a.novel_in_window + b.novel_in_window,
        seen_in_window=a.seen_in_window + b.seen_in_window,
        total_seen=a.total_seen + b.total_seen,
        total_evictions=a.total_evictions + b.total_evictions,
        total_writes=a.total_writes + b.total_writes,
    )
