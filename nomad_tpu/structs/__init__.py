"""Data model + wire structs (reference: nomad/structs/)."""

from .structs import (  # explicit re-exports for the commonly used names
    Allocation, AllocListStub, AllocMetric, CheckState, ColumnarPlacements,
    Constraint, DesiredUpdates, columns_only,
    Evaluation, Job, JobListStub, JobPlanResponse, LogConfig, NetworkResource, Node,
    NodeListStub, PeriodicConfig, PeriodicLaunch, Plan, PlanAnnotations,
    PlanResult, Port, Resources, RestartPolicy, Service, ServiceCheck,
    ServiceRegistration, Task,
    TaskArtifact, TaskEvent, TaskGroup, TaskState, UpdateStrategy,
    ValidationError, generate_uuid, generate_uuids, job_stub, placed_count,
    column_list, stamp_alloc, uuid_rows, uuid_strings,
)
from .bitmap import Bitmap  # noqa: F401
from .funcs import allocs_fit, filter_terminal_allocs, remove_allocs, score_fit  # noqa: F401
from .network import NetworkIndex  # noqa: F401
from .node_class import (  # noqa: F401
    compute_class, compute_node_class, escaped_constraints, is_unique_namespace,
    unique_namespace,
)
from .codec import decode, encode, from_dict, to_dict  # noqa: F401
