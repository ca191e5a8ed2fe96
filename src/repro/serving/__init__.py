"""Conversational serving runtime: session engines + scheduler + router.

Sequential path: ``engine.ConversationalSearchEngine`` (one turn per
dispatch).  Batched path: ``engine.BatchedConversationalSearchEngine``
(continuously micro-batched flushes over a device-resident
``sessions.SessionStore`` slab).  Replicated path:
``router.ReplicatedSearchEngine`` (session-affine routing over the
replica axis of a 2-D corpus mesh, with cross-replica hedging for
stateless traffic).  ``scheduler`` supplies the batching/hedging
front door.
"""
from repro.serving import (  # noqa: F401
    engine, result_cache, router, scheduler, sessions, telemetry)
from repro.serving.engine import (  # noqa: F401
    BatchedConversationalSearchEngine, ConversationalSearchEngine,
    ServingConfig, TurnRecord)
from repro.serving.result_cache import (  # noqa: F401
    CacheEntry, ResultCache)
from repro.serving.router import ReplicatedSearchEngine  # noqa: F401
from repro.serving.scheduler import (  # noqa: F401
    HedgedExecutor, MicroBatcher, Request)
from repro.serving.sessions import (  # noqa: F401
    SessionStore, hnsw_session_store, ivf_pq_session_store,
    ivf_session_store, store_for_backend)
from repro.serving.telemetry import Telemetry  # noqa: F401
