"""Device-tier batched dispatch: VectorGrain, sharded actor tables, tick
engine (the TPU-native replacement for the reference's per-message hot path,
SURVEY.md §7)."""

from .engine import VectorActorRef, VectorRuntime  # noqa: F401
from .hosting import add_vector_grains  # noqa: F401
from .replicated import ReplicatedWorkerHost, replicated_worker  # noqa: F401
from .reshard import reshard_dense  # noqa: F401
from .table import ShardedActorTable  # noqa: F401
from .vector_grain import VectorGrain, actor_method, sends  # noqa: F401
