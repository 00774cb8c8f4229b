"""Distributed runtime: Manager-Worker demand-driven dispatch behind the
transport-agnostic WorkerBackend boundary (threads or RPC worker
processes), hierarchical storage, fault tolerance (heartbeats/retry/backup
tasks), elastic scaling, and the paper-scale cluster simulator."""

from repro.runtime.fairshare import FairQueue, TaskCancelled  # noqa: F401
from repro.runtime.manager import Manager, WorkItem, run_study_distributed  # noqa: F401
from repro.runtime.net import (  # noqa: F401
    SocketBackend,
    run_worker,
    socket_flag_kwargs,
)
from repro.runtime.objstore import (  # noqa: F401
    InMemoryObjectStore,
    LocalFSObjectStore,
    ObjectBackedStore,
    ObjectStore,
)
from repro.runtime.transport import (  # noqa: F401
    Completion,
    Lease,
    ProcessRpcBackend,
    RemoteTaskError,
    ThreadBackend,
    TransportError,
    WorkerBackend,
    WorkerStatus,
    make_backend,
)
from repro.runtime.simulator import (  # noqa: F401
    AutotuneResult,
    ClusterSim,
    StreamSim,
    autotune_stream,
    simulate_cluster,
    simulate_stream,
)
from repro.runtime.storage import (  # noqa: F401
    HierarchicalStore,
    SharedStore,
    mount_store,
)
