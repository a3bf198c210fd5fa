"""Grain persistence providers (reference L11 persistence) + device-tier
checkpoint/resume (orbax table snapshots, write-behind row persistence)."""

from .checkpoint import VectorCheckpointer, VectorStorageBridge  # noqa: F401
from .core import (  # noqa: F401
    ADOPT_ETAG,
    ErrorInjectionStorage,
    FileStorage,
    GrainStorage,
    LatencyStorage,
    MemoryStorage,
    StateStorageBridge,
    StorageManager,
)
