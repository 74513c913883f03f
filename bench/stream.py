"""The benchmark's own copy of the paper's Twitter-shaped stream.

A drifting, Zipf-weighted topic mixture of unit embeddings with bursts and
an isotropic background share, as ``repro.data.streams.TopicStream`` with
its ``"twitter"`` parameters (256 topics, Zipf 1.2, drift 0.03, bursts
0.15, noise 0.45, 20% background). It is copied here so that a change to
the program cannot change the traffic it is measured on. numpy only.
"""
from __future__ import annotations

import numpy as np

TWITTER = dict(n_topics=256, zipf_s=1.2, drift=0.03, burstiness=0.15,
               noise=0.45, background_frac=0.20, anisotropy=1.0)


class TopicStream:
    """Drifting Zipf-weighted topic-mixture embedding stream."""

    def __init__(self, dim: int, seed: int, n_topics: int, zipf_s: float,
                 drift: float, burstiness: float, noise: float,
                 background_frac: float, anisotropy: float):
        self.dim, self.n_topics = dim, n_topics
        self.drift, self.burstiness = drift, burstiness
        self.noise, self.background_frac = noise, background_frac
        self.seed = seed
        self.rng = np.random.default_rng(seed)   # the stream's dynamics
        self.batches = 0
        self.query_calls = 0
        g0 = self.rng.normal(size=dim)
        self.corpus_mean = g0 / np.linalg.norm(g0)
        m = (self.rng.normal(size=(n_topics, dim))
             + anisotropy * np.sqrt(dim) * self.corpus_mean)
        self.means = m / np.linalg.norm(m, axis=1, keepdims=True)
        w = 1.0 / np.arange(1, n_topics + 1) ** max(zipf_s, 1e-3)
        self.rng.shuffle(w)
        self.base_weights = w / w.sum()
        self.spike = np.ones(n_topics)
        self.next_id = 0

    def _advance(self):
        if self.drift > 0:
            step = self.rng.normal(size=self.means.shape) * self.drift
            self.means = self.means + step
            self.means += 0.1 * self.drift * np.sqrt(self.dim) \
                * self.corpus_mean
            self.means /= np.linalg.norm(self.means, axis=1, keepdims=True)
        if self.burstiness > 0:
            self.spike *= 0.9
            self.spike = np.maximum(self.spike, 1.0)
            burst = (self.rng.random(self.n_topics)
                     < self.burstiness / self.n_topics)
            self.spike[burst] = 10.0

    def weights(self) -> np.ndarray:
        w = self.base_weights * self.spike
        return w / w.sum()

    @staticmethod
    def _unit(rng, n: int, dim: int) -> np.ndarray:
        eps = rng.standard_normal((n, dim), dtype=np.float32)
        return eps / np.linalg.norm(eps, axis=1, keepdims=True)

    def plan(self, batch: int) -> tuple:
        """The sequential part of the next batch: the stream's dynamics
        (drift, bursts, topic and background draws) from its one
        generator. ``make`` turns a plan into the batch."""
        self._advance()
        topics = self.rng.choice(self.n_topics, size=batch, p=self.weights())
        bg = np.nonzero(self.rng.random(batch) < self.background_frac)[0]
        j = self.batches
        self.batches += 1
        ids = np.arange(self.next_id, self.next_id + batch, dtype=np.int32)
        self.next_id += batch
        return j, self.means.astype(np.float32), topics, bg, ids

    def make(self, plan: tuple) -> tuple[np.ndarray, np.ndarray]:
        """(embeddings [batch, dim] f32 unit rows, doc ids [batch] i32) of
        a planned batch. Its noise comes from a generator of its own,
        keyed by (seed, batch number), so plans can be made in any order
        and on several threads. The docs are drawn in float32."""
        j, means, topics, bg, ids = plan
        noise = np.random.default_rng([self.seed, 1, j])
        x = means[topics] * np.float32(1 - self.noise) \
            + np.float32(self.noise) * self._unit(noise, len(ids), self.dim)
        x[bg] = noise.standard_normal((bg.size, self.dim), dtype=np.float32)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        return x, ids

    def next_batch(self, batch: int) -> tuple[np.ndarray, np.ndarray]:
        return self.make(self.plan(batch))

    def queries(self, n: int) -> np.ndarray:
        """[n, dim] f32 unit queries from the current topic mix."""
        topics = self.rng.choice(self.n_topics, size=n, p=self.weights())
        noise = np.random.default_rng([self.seed, 2, self.query_calls])
        self.query_calls += 1
        half = np.float32(self.noise * 0.5)
        q = self.means.astype(np.float32)[topics] * (1 - half) \
            + half * self._unit(noise, n, self.dim)
        return q / np.linalg.norm(q, axis=1, keepdims=True)


def make_stream(kind: str, dim: int, seed: int) -> TopicStream:
    if kind != "twitter":
        raise ValueError(f"unknown stream {kind!r}")
    return TopicStream(dim, seed, **TWITTER)
