"""Serving layer: micro-batching query front end over the engine protocol.

``runtime.AsyncServer`` — the server: background ingest thread + atomic
                         snapshot publication; queries never block on
                         ingest or reconcile. Supervised (bounded
                         restarts, poison quarantine) and optionally
                         durable.
``hotset.HotSet``       — query-side heavy-hitter hot set + pinned
                         fast-tier serving (Level 1 of the serving cache).
``result_cache.ResultCache`` — snapshot-versioned exact result cache with
                         precise delta invalidation (Level 2).
``durability``          — write-ahead ingest journal + incremental engine
                         checkpoints; recovery replays the journal tail
                         bit-identical to the never-crashed engine.
"""
from repro.serve.durability import (CheckpointStore,  # noqa: F401
                                    DurabilityConfig, DurableIngest,
                                    IngestJournal, classify_error,
                                    replay_journal)
from repro.serve.hotset import HotSet  # noqa: F401
from repro.serve.result_cache import ResultCache  # noqa: F401
from repro.serve.runtime import AsyncServer, ServerConfig  # noqa: F401
