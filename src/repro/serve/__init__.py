from ..sched.service import WorkflowService
from ..sched.stats import AggregateStats
from .engine import GenStats, ServeEngine, ServeMetrics
from .snapshots import FabricSnapshotStore, LoadedSnapshot

__all__ = [
    "AggregateStats",
    "FabricSnapshotStore",
    "GenStats",
    "LoadedSnapshot",
    "ServeEngine",
    "ServeMetrics",
    "WorkflowService",
]
