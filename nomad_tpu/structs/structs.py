"""Core data model: jobs, nodes, allocations, evaluations, plans.

Semantics mirror the reference data model (reference: nomad/structs/structs.go)
— same field names (wire compatibility), same statuses, same validation rules —
but the implementation is new. Durations are integer nanoseconds, matching the
reference's Go time.Duration wire encoding.
"""

from __future__ import annotations

import copy
import re
import threading
import time as _time
import os
from dataclasses import dataclass, field, replace
from collections.abc import KeysView
from typing import Any, Dict, List, Optional

import numpy as np

# --- Duration helpers (Go time.Duration is int64 nanoseconds on the wire) ---
NANOSECOND = 1
MICROSECOND = 1000 * NANOSECOND
MILLISECOND = 1000 * MICROSECOND
SECOND = 1000 * MILLISECOND
MINUTE = 60 * SECOND
HOUR = 60 * MINUTE


def ns_to_seconds(ns: int) -> float:
    return ns / SECOND


# --- Statuses and constants (reference: structs.go:547-549, 907-916,
#     1936-1938, 2294-2304, 2598-2612, 2620-2634) ---
NodeStatusInit = "initializing"
NodeStatusReady = "ready"
NodeStatusDown = "down"

JobTypeCore = "_core"
JobTypeService = "service"
JobTypeBatch = "batch"
JobTypeSystem = "system"

JobStatusPending = "pending"
JobStatusRunning = "running"
JobStatusDead = "dead"

JobMinPriority = 1
JobDefaultPriority = 50
JobMaxPriority = 100

CoreJobPriority = JobMaxPriority * 2

TaskStatePending = "pending"
TaskStateRunning = "running"
TaskStateDead = "dead"

TaskDriverFailure = "Driver Failure"
TaskReceived = "Received"
TaskFailedValidation = "Failed Validation"
TaskStarted = "Started"
TaskTerminated = "Terminated"
TaskKilled = "Killed"
TaskRestarting = "Restarting"
TaskNotRestarting = "Not Restarting"
TaskDownloadingArtifacts = "Downloading Artifacts"
TaskArtifactDownloadFailed = "Failed Artifact Download"

AllocDesiredStatusRun = "run"
AllocDesiredStatusStop = "stop"
AllocDesiredStatusEvict = "evict"
AllocDesiredStatusFailed = "failed"

AllocClientStatusPending = "pending"
AllocClientStatusRunning = "running"
AllocClientStatusComplete = "complete"
AllocClientStatusFailed = "failed"

EvalStatusBlocked = "blocked"
EvalStatusPending = "pending"
EvalStatusComplete = "complete"
EvalStatusFailed = "failed"
EvalStatusCancelled = "canceled"

EvalTriggerJobRegister = "job-register"
EvalTriggerJobDeregister = "job-deregister"
EvalTriggerPeriodicJob = "periodic-job"
EvalTriggerNodeUpdate = "node-update"
EvalTriggerScheduled = "scheduled"
EvalTriggerRollingUpdate = "rolling-update"
EvalTriggerMaxPlans = "max-plan-attempts"

CoreJobEvalGC = "eval-gc"
CoreJobNodeGC = "node-gc"
CoreJobJobGC = "job-gc"
CoreJobForceGC = "force-gc"

ConstraintDistinctHosts = "distinct_hosts"
ConstraintRegex = "regexp"
ConstraintVersion = "version"

RestartPolicyModeDelay = "delay"
RestartPolicyModeFail = "fail"

PeriodicSpecCron = "cron"
PeriodicSpecTest = "_internal_test"
PeriodicLaunchSuffix = "/periodic-"

ServiceCheckHTTP = "http"
ServiceCheckTCP = "tcp"
ServiceCheckScript = "script"

DefaultKillTimeout = 5 * SECOND

MinDynamicPort = 20000
MaxDynamicPort = 60000
MaxValidPort = 65536

# Reserved eval IDs used by plans (reference: structs.go:2849-2861)
EvalIdNotBlocked = ""


_UUID_POOL: List[str] = []


def generate_uuid() -> str:
    """Random UUID for IDs (reference: structs.go GenerateUUID, which
    likewise formats crypto/rand bytes directly). IDs are minted per
    placement on the scheduling path, so entropy is drawn in one syscall
    per 512 IDs instead of one urandom read each (a 64-eval storm window
    mints ~3200 — at 64 IDs per draw the urandom syscalls alone were a
    visible slice of the measured t_collect_ms)."""
    try:
        h = _UUID_POOL.pop()  # list.pop is GIL-atomic
    except IndexError:
        hx = os.urandom(16 * 512).hex()
        _UUID_POOL.extend(hx[i:i + 32] for i in range(32, len(hx), 32))
        h = hx[:32]
    # RFC 4122 v4 shape (version/variant nibbles fixed).
    return (f"{h[:8]}-{h[8:12]}-4{h[13:16]}-"
            f"{'89ab'[int(h[16], 16) & 3]}{h[17:20]}-{h[20:]}")


# The version and variant bits of RFC 4122 v4, set on a whole draw at once.
_UUID_VERSION = bytes((b & 0x0F) | 0x40 for b in range(256))
_UUID_VARIANT = bytes((b & 0x3F) | 0x80 for b in range(256))


def uuid_rows(n: int) -> np.ndarray:
    """`n` IDs of generate_uuid's shape from ONE os.urandom draw, as the
    rows of a [n, 37] matrix of ASCII bytes (36 characters and a newline):
    a column that is permuted or sliced as bytes before any string of it
    exists. uuid_strings makes the strings."""
    raw = bytearray(os.urandom(16 * n))
    raw[6::16] = raw[6::16].translate(_UUID_VERSION)
    raw[8::16] = raw[8::16].translate(_UUID_VARIANT)
    hx = np.frombuffer(raw.hex().encode("ascii"), np.uint8).reshape(n, 32)
    out = np.empty((n, 37), np.uint8)
    out[:, 8] = out[:, 13] = out[:, 18] = out[:, 23] = 0x2D  # "-"
    out[:, 36] = 0x0A
    out[:, 0:8] = hx[:, 0:8]
    out[:, 9:13] = hx[:, 8:12]
    out[:, 14:18] = hx[:, 12:16]
    out[:, 19:23] = hx[:, 16:20]
    out[:, 24:36] = hx[:, 20:32]
    return out


def uuid_strings(rows: np.ndarray) -> List[str]:
    """The IDs of uuid_rows' rows (or of any selection of them), in row
    order: one decode and one split for the lot."""
    return rows.tobytes().decode("ascii").splitlines()


def generate_uuids(n: int) -> List[str]:
    """`n` IDs of generate_uuid's shape from ONE os.urandom draw: what a
    window's build mints for its placements, as a column."""
    return uuid_strings(uuid_rows(n))


class ValidationError(Exception):
    """Aggregated validation failure (reference: go-multierror usage)."""

    def __init__(self, errors: List[str]):
        self.errors = errors
        super().__init__("; ".join(errors))


# ---------------------------------------------------------------------------
# Resources
# ---------------------------------------------------------------------------


@dataclass
class Port:
    Label: str = ""
    Value: int = 0


@dataclass
class NetworkResource:
    """Network ask/offer on a device (reference: structs.go:840-905)."""

    Device: str = ""
    CIDR: str = ""
    IP: str = ""
    MBits: int = 0
    ReservedPorts: List[Port] = field(default_factory=list)
    DynamicPorts: List[Port] = field(default_factory=list)

    def copy(self) -> "NetworkResource":
        # Manual copy: this runs once per chosen placement on the scheduling
        # hot path; deepcopy's reflective walk is ~20x slower.
        return NetworkResource(
            Device=self.Device, CIDR=self.CIDR, IP=self.IP, MBits=self.MBits,
            ReservedPorts=[Port(p.Label, p.Value) for p in self.ReservedPorts],
            DynamicPorts=[Port(p.Label, p.Value) for p in self.DynamicPorts])

    def add(self, delta: "NetworkResource") -> None:
        self.ReservedPorts.extend(Port(p.Label, p.Value)
                                  for p in delta.ReservedPorts)
        self.MBits += delta.MBits
        self.DynamicPorts.extend(Port(p.Label, p.Value)
                                 for p in delta.DynamicPorts)

    def meets_min_resources(self) -> List[str]:
        errs = []
        if self.MBits < 1:
            errs.append(f"minimum MBits value is 1; got {self.MBits}")
        return errs

    def port_labels(self) -> Dict[str, int]:
        labels: Dict[str, int] = {}
        for p in self.ReservedPorts:
            labels[p.Label] = p.Value
        for p in self.DynamicPorts:
            labels[p.Label] = p.Value
        return labels


@dataclass
class Resources:
    """Resource ask/capacity (reference: structs.go:698-838)."""

    CPU: int = 0  # MHz
    MemoryMB: int = 0
    DiskMB: int = 0
    IOPS: int = 0
    Networks: List[NetworkResource] = field(default_factory=list)

    @staticmethod
    def default() -> "Resources":
        return Resources(CPU=100, MemoryMB=10, DiskMB=300, IOPS=0)

    def copy(self) -> "Resources":
        # Hot path: one copy per task per placement (stack._assign_networks).
        return Resources(CPU=self.CPU, MemoryMB=self.MemoryMB,
                         DiskMB=self.DiskMB, IOPS=self.IOPS,
                         Networks=[n.copy() for n in self.Networks])

    def merge(self, other: "Resources") -> None:
        if other.CPU:
            self.CPU = other.CPU
        if other.MemoryMB:
            self.MemoryMB = other.MemoryMB
        if other.DiskMB:
            self.DiskMB = other.DiskMB
        if other.IOPS:
            self.IOPS = other.IOPS
        if other.Networks:
            self.Networks = other.Networks

    def meets_min_resources(self) -> List[str]:
        errs = []
        if self.CPU < 20:
            errs.append(f"minimum CPU value is 20; got {self.CPU}")
        if self.MemoryMB < 10:
            errs.append(f"minimum MemoryMB value is 10; got {self.MemoryMB}")
        if self.DiskMB < 10:
            errs.append(f"minimum DiskMB value is 10; got {self.DiskMB}")
        if self.IOPS < 0:
            errs.append(f"minimum IOPS value is 0; got {self.IOPS}")
        for i, n in enumerate(self.Networks):
            for e in n.meets_min_resources():
                errs.append(f"network resource at index {i} failed: {e}")
        return errs

    def net_index(self, n: NetworkResource) -> int:
        for idx, net in enumerate(self.Networks):
            if net.Device == n.Device:
                return idx
        return -1

    def superset(self, other: "Resources") -> tuple[bool, str]:
        """Fit check; ignores networks (use NetworkIndex for those)."""
        if self.CPU < other.CPU:
            return False, "cpu exhausted"
        if self.MemoryMB < other.MemoryMB:
            return False, "memory exhausted"
        if self.DiskMB < other.DiskMB:
            return False, "disk exhausted"
        if self.IOPS < other.IOPS:
            return False, "iops exhausted"
        return True, ""

    def add(self, delta: Optional["Resources"]) -> None:
        if delta is None:
            return
        self.CPU += delta.CPU
        self.MemoryMB += delta.MemoryMB
        self.DiskMB += delta.DiskMB
        self.IOPS += delta.IOPS
        for n in delta.Networks:
            idx = self.net_index(n)
            if idx == -1:
                self.Networks.append(n.copy())
            else:
                self.Networks[idx].add(n)


# ---------------------------------------------------------------------------
# Constraints
# ---------------------------------------------------------------------------


@dataclass
class Constraint:
    """Scheduling constraint (reference: structs.go:2249-2291)."""

    LTarget: str = ""
    RTarget: str = ""
    Operand: str = ""

    def __str__(self) -> str:
        return f"{self.LTarget} {self.Operand} {self.RTarget}"

    def validate(self) -> List[str]:
        errs = []
        if not self.Operand:
            errs.append("Missing constraint operand")
        if self.Operand == ConstraintRegex:
            try:
                re.compile(self.RTarget)
            except re.error as e:
                errs.append(f"Regular expression failed to compile: {e}")
        elif self.Operand == ConstraintVersion:
            from .version import parse_version_constraint

            try:
                parse_version_constraint(self.RTarget)
            except ValueError as e:
                errs.append(f"Version constraint is invalid: {e}")
        return errs


# ---------------------------------------------------------------------------
# Services
# ---------------------------------------------------------------------------


@dataclass
class ServiceCheck:
    """Consul-style health check (reference: structs.go:1494-1560)."""

    Name: str = ""
    Type: str = ""
    Command: str = ""
    Args: List[str] = field(default_factory=list)
    Path: str = ""
    Protocol: str = ""
    Interval: int = 0  # ns
    Timeout: int = 0  # ns

    def validate(self) -> List[str]:
        errs = []
        t = self.Type.lower()
        if t not in (ServiceCheckTCP, ServiceCheckHTTP, ServiceCheckScript):
            errs.append(f'service check must be either http, tcp or script type, got: "{self.Type}"')
            return errs
        if t == ServiceCheckHTTP and not self.Path:
            errs.append("service checks of http type must have a valid http path")
        if t == ServiceCheckScript and not self.Command:
            errs.append("service checks of script type must have a valid script path")
        if self.Interval < 10 * SECOND:
            errs.append("interval must be at least 10s")
        return errs

    def requires_port(self) -> bool:
        return self.Type.lower() in (ServiceCheckHTTP, ServiceCheckTCP)


@dataclass
class Service:
    """Service registration spec (reference: structs.go:1563-1676)."""

    Name: str = ""
    Tags: List[str] = field(default_factory=list)
    PortLabel: str = ""
    Checks: List[ServiceCheck] = field(default_factory=list)

    _VALID_NAME = re.compile(r"^[a-zA-Z0-9\-]+$")

    def init_fields(self, job: str, task_group: str, task: str) -> None:
        self.Name = (
            self.Name.replace("${JOB}", job)
            .replace("${TASKGROUP}", task_group)
            .replace("${TASK}", task)
        )
        if not self.Name:
            self.Name = f"{job}-{task_group}-{task}"
        for check in self.Checks:
            if not check.Name:
                check.Name = f"service: {self.Name!r} check"

    def validate(self) -> List[str]:
        errs = []
        if not Service._VALID_NAME.match(self.Name):
            errs.append(
                f"service name must be valid per {Service._VALID_NAME.pattern!r}; got {self.Name!r}"
            )
        for check in self.Checks:
            for e in check.validate():
                errs.append(f"check {check.Name} validation failed: {e}")
            if not self.PortLabel and check.requires_port():
                errs.append(f"check {check.Name} is a {check.Type} check but the service has no port")
        return errs


# Service registry check/instance statuses. The registry is this framework's
# standalone replacement for the reference's external Consul dependency
# (command/agent/consul/syncer.go): registrations live in the replicated
# state store and are queryable cluster-wide with blocking queries.
CheckStatusPassing = "passing"
CheckStatusWarning = "warning"
CheckStatusCritical = "critical"
CheckStatusUnknown = "unknown"


@dataclass
class CheckState:
    """Latest result of one health check run against a registered service."""

    Name: str = ""
    Type: str = ""
    Status: str = CheckStatusUnknown
    Output: str = ""
    Timestamp: float = 0.0


@dataclass
class ServiceRegistration:
    """One live instance of a service in the cluster registry.

    The reference registers AgentServiceRegistrations with the node-local
    Consul agent (consul/syncer.go:723-743); here the registration is a
    first-class replicated object written through the FSM, so discovery
    queries hit the same MVCC store as everything else.
    """

    ID: str = ""           # unique instance id (alloc+task+service, or agent)
    ServiceName: str = ""
    Tags: List[str] = field(default_factory=list)
    JobID: str = ""
    AllocID: str = ""
    TaskName: str = ""
    NodeID: str = ""
    Address: str = ""
    Port: int = 0
    Status: str = CheckStatusUnknown  # worst check status; passing if no checks
    Checks: List[CheckState] = field(default_factory=list)
    CreateIndex: int = 0
    ModifyIndex: int = 0

    def copy(self) -> "ServiceRegistration":
        out = replace(self)
        out.Tags = list(self.Tags)
        out.Checks = [replace(c) for c in self.Checks]
        return out

    def derive_status(self) -> str:
        """Worst-of over check states (Consul health aggregation order)."""
        if not self.Checks:
            return CheckStatusPassing
        order = (CheckStatusCritical, CheckStatusUnknown, CheckStatusWarning,
                 CheckStatusPassing)
        for status in order:
            if any(c.Status == status for c in self.Checks):
                return status
        return CheckStatusUnknown


# ---------------------------------------------------------------------------
# Tasks
# ---------------------------------------------------------------------------


@dataclass
class LogConfig:
    """Task log rotation config (reference: structs.go:1678-1702)."""

    MaxFiles: int = 10
    MaxFileSizeMB: int = 10

    def validate(self) -> List[str]:
        errs = []
        if self.MaxFiles < 1:
            errs.append(f"minimum number of files is 1; got {self.MaxFiles}")
        if self.MaxFileSizeMB < 1:
            errs.append(f"minimum file size is 1MB; got {self.MaxFileSizeMB}")
        return errs


@dataclass
class TaskArtifact:
    """Remote artifact to fetch into the task dir (reference: structs.go:2142-2240)."""

    GetterSource: str = ""
    GetterOptions: Dict[str, str] = field(default_factory=dict)
    RelativeDest: str = "local/"

    def validate(self) -> List[str]:
        errs = []
        if not self.GetterSource:
            errs.append("source must be specified")
        # Verify the destination doesn't escape the task's directory.
        import posixpath

        dest = posixpath.normpath(posixpath.join("/", self.RelativeDest))
        if not dest.startswith("/"):
            errs.append("destination escapes task's directory")
        return errs


@dataclass
class Task:
    """A unit of work executed by a driver (reference: structs.go:1704-1934)."""

    Name: str = ""
    Driver: str = ""
    User: str = ""
    Config: Dict[str, Any] = field(default_factory=dict)
    Env: Dict[str, str] = field(default_factory=dict)
    Services: List[Service] = field(default_factory=list)
    Constraints: List[Constraint] = field(default_factory=list)
    Resources: Optional[Resources] = None
    Meta: Dict[str, str] = field(default_factory=dict)
    KillTimeout: int = DefaultKillTimeout  # ns
    LogConfig: Optional[LogConfig] = None
    Artifacts: List[TaskArtifact] = field(default_factory=list)

    _VALID_NAME = re.compile(r"^[a-zA-Z0-9\-_]{1,128}$")

    def copy(self) -> "Task":
        return copy.deepcopy(self)

    def init_fields(self, job: "Job", tg: "TaskGroup") -> None:
        if self.LogConfig is None:
            self.LogConfig = LogConfig()
        for service in self.Services:
            service.init_fields(job.Name, tg.Name, self.Name)

    def validate(self) -> List[str]:
        errs = []
        if not self.Name:
            errs.append("Missing task name")
        elif not Task._VALID_NAME.match(self.Name):
            errs.append(
                "Task name must consist of alphanumeric characters, dashes or underscores"
            )
        if not self.Driver:
            errs.append("Missing task driver")
        if self.KillTimeout < 0:
            errs.append("KillTimeout must be a positive value")
        if self.Resources is None:
            errs.append("Missing task resources")
        else:
            errs.extend(self.Resources.meets_min_resources())
            # Ensure the task isn't asking for disk in networks.
            labels: Dict[str, int] = {}
            for net in self.Resources.Networks:
                for port in list(net.ReservedPorts) + list(net.DynamicPorts):
                    if port.Label in labels:
                        errs.append(f"Port label {port.Label} used more than once")
                    labels[port.Label] = port.Value
            for service in self.Services:
                if service.PortLabel and service.PortLabel not in labels:
                    errs.append(
                        f"port label {service.PortLabel!r} referenced by service {service.Name!r} does not exist"
                    )
        if self.LogConfig is not None and self.Resources is not None:
            log_usage = self.LogConfig.MaxFiles * self.LogConfig.MaxFileSizeMB
            if self.Resources.DiskMB <= log_usage:
                errs.append(
                    f"log storage ({log_usage} MB) must be less than requested disk capacity ({self.Resources.DiskMB} MB)"
                )
        for i, constr in enumerate(self.Constraints):
            for e in constr.validate():
                errs.append(f"Constraint {i + 1} validation failed: {e}")
        for service in self.Services:
            errs.extend(service.validate())
        if self.LogConfig is not None:
            errs.extend(self.LogConfig.validate())
        for i, artifact in enumerate(self.Artifacts):
            for e in artifact.validate():
                errs.append(f"Artifact {i + 1} validation failed: {e}")
        return errs


@dataclass
class TaskState:
    """Client-side task lifecycle state (reference: structs.go:1941-1998)."""

    State: str = TaskStatePending
    Events: List["TaskEvent"] = field(default_factory=list)

    def successful(self) -> bool:
        if self.State != TaskStateDead:
            return False
        if not self.Events:
            return False
        last = self.Events[-1]
        return last.Type == TaskTerminated and last.ExitCode == 0


@dataclass
class TaskEvent:
    """Typed task lifecycle event (reference: structs.go:2037-2140)."""

    Type: str = ""
    Time: int = 0  # unix nanoseconds
    RestartReason: str = ""
    DriverError: str = ""
    ExitCode: int = 0
    Signal: int = 0
    Message: str = ""
    KillError: str = ""
    StartDelay: int = 0
    DownloadError: str = ""
    ValidationError: str = ""

    @staticmethod
    def new(event_type: str) -> "TaskEvent":
        return TaskEvent(Type=event_type, Time=_time.time_ns())


# ---------------------------------------------------------------------------
# Task groups and jobs
# ---------------------------------------------------------------------------


@dataclass
class RestartPolicy:
    """Task restart policy (reference: structs.go:1280-1366)."""

    Attempts: int = 0
    Interval: int = 0  # ns
    Delay: int = 0  # ns
    Mode: str = RestartPolicyModeDelay

    @staticmethod
    def for_job_type(job_type: str) -> Optional["RestartPolicy"]:
        if job_type in (JobTypeService, JobTypeSystem):
            return RestartPolicy(Attempts=2, Interval=1 * MINUTE, Delay=15 * SECOND,
                                 Mode=RestartPolicyModeDelay)
        if job_type == JobTypeBatch:
            return RestartPolicy(Attempts=15, Interval=7 * 24 * HOUR, Delay=15 * SECOND,
                                 Mode=RestartPolicyModeDelay)
        return None

    def validate(self) -> List[str]:
        errs = []
        if self.Mode not in (RestartPolicyModeDelay, RestartPolicyModeFail):
            errs.append(f"Unsupported restart mode: {self.Mode!r}")
            return errs
        if self.Attempts == 0 and self.Mode != RestartPolicyModeFail:
            errs.append(f"Restart policy {self.Mode!r} with {self.Attempts} attempts is ambiguous")
        if self.Interval == 0:
            return errs
        if self.Attempts * self.Delay > self.Interval:
            errs.append(
                f"Nomad can't restart the TaskGroup {self.Attempts} times in an interval "
                f"of {self.Interval} with a delay of {self.Delay}"
            )
        return errs


@dataclass
class TaskGroup:
    """Atomic unit of placement (reference: structs.go:1368-1488)."""

    Name: str = ""
    Count: int = 1
    Constraints: List[Constraint] = field(default_factory=list)
    RestartPolicy: Optional[RestartPolicy] = None
    Tasks: List[Task] = field(default_factory=list)
    Meta: Dict[str, str] = field(default_factory=dict)

    _VALID_NAME = Task._VALID_NAME

    def copy(self) -> "TaskGroup":
        return copy.deepcopy(self)

    def init_fields(self, job: "Job") -> None:
        if self.RestartPolicy is None:
            self.RestartPolicy = RestartPolicy.for_job_type(job.Type)
        for task in self.Tasks:
            task.init_fields(job, self)

    def validate(self) -> List[str]:
        errs = []
        if not self.Name:
            errs.append("Missing task group name")
        elif not TaskGroup._VALID_NAME.match(self.Name):
            errs.append(
                "Task group name must consist of alphanumeric characters, dashes or underscores"
            )
        if self.Count <= 0:
            errs.append("Task group count must be positive")
        if not self.Tasks:
            errs.append("Missing tasks for task group")
        for i, constr in enumerate(self.Constraints):
            for e in constr.validate():
                errs.append(f"Constraint {i + 1} validation failed: {e}")
        if self.RestartPolicy is not None:
            errs.extend(self.RestartPolicy.validate())
        else:
            errs.append("Task Group must have a restart policy")
        tasks: Dict[str, int] = {}
        for idx, task in enumerate(self.Tasks):
            if task.Name in tasks:
                errs.append(f"Task {task.Name} defined multiple times")
            tasks[task.Name] = idx
        for task in self.Tasks:
            for e in task.validate():
                errs.append(f"Task {task.Name} validation failed: {e}")
        return errs

    def lookup_task(self, name: str) -> Optional[Task]:
        for t in self.Tasks:
            if t.Name == name:
                return t
        return None


@dataclass
class UpdateStrategy:
    """Rolling-update config (reference: structs.go:1152-1168)."""

    Stagger: int = 0  # ns
    MaxParallel: int = 0

    def rolling(self) -> bool:
        return self.Stagger > 0 and self.MaxParallel > 0


@dataclass
class PeriodicConfig:
    """Periodic (cron) launch config (reference: structs.go:1177-1266)."""

    Enabled: bool = False
    Spec: str = ""
    SpecType: str = PeriodicSpecCron
    ProhibitOverlap: bool = False

    def validate(self) -> List[str]:
        if not self.Enabled:
            return []
        errs = []
        if not self.Spec:
            errs.append("Must specify a spec")
            return errs
        if self.SpecType == PeriodicSpecCron:
            from .cron import CronExpr

            try:
                CronExpr.parse(self.Spec)
            except ValueError as e:
                errs.append(f"Invalid cron spec {self.Spec!r}: {e}")
        elif self.SpecType == PeriodicSpecTest:
            pass
        else:
            errs.append(f"Unknown periodic specification type {self.SpecType!r}")
        return errs

    def next(self, from_time: float) -> float:
        """Next launch time (unix seconds) strictly after from_time.

        Returns 0.0 when there is no next launch (reference: structs.go:1243-1263).
        """
        if self.SpecType == PeriodicSpecCron:
            from .cron import CronExpr

            return CronExpr.parse(self.Spec).next(from_time)
        if self.SpecType == PeriodicSpecTest:
            if not self.Spec:
                return 0.0
            times = [float(s) for s in self.Spec.split(",") if s]
            for t in times:
                if t > from_time:
                    return t
            return 0.0
        return 0.0


@dataclass
class Job:
    """Declarative workload specification (reference: structs.go:940-1150)."""

    Region: str = ""
    ID: str = ""
    ParentID: str = ""
    Name: str = ""
    Type: str = ""
    Priority: int = 0
    AllAtOnce: bool = False
    Datacenters: List[str] = field(default_factory=list)
    Constraints: List[Constraint] = field(default_factory=list)
    TaskGroups: List[TaskGroup] = field(default_factory=list)
    Update: UpdateStrategy = field(default_factory=UpdateStrategy)
    Periodic: Optional[PeriodicConfig] = None
    Meta: Dict[str, str] = field(default_factory=dict)
    Status: str = ""
    StatusDescription: str = ""
    CreateIndex: int = 0
    ModifyIndex: int = 0
    JobModifyIndex: int = 0

    def init_fields(self) -> None:
        for tg in self.TaskGroups:
            tg.init_fields(self)

    def copy(self) -> "Job":
        return copy.deepcopy(self)

    def validate(self) -> List[str]:
        errs = []
        if not self.Region:
            errs.append("Missing job region")
        if not self.ID:
            errs.append("Missing job ID")
        elif " " in self.ID:
            errs.append("Job ID contains a space")
        if not self.Name:
            errs.append("Missing job name")
        if not self.Type:
            errs.append("Missing job type")
        if self.Priority < JobMinPriority or self.Priority > JobMaxPriority:
            errs.append(f"Job priority must be between [{JobMinPriority}, {JobMaxPriority}]")
        if not self.Datacenters:
            errs.append("Missing job datacenters")
        if not self.TaskGroups:
            errs.append("Missing job task groups")
        for idx, constr in enumerate(self.Constraints):
            for e in constr.validate():
                errs.append(f"Constraint {idx + 1} validation failed: {e}")

        taskGroups: Dict[str, int] = {}
        for idx, tg in enumerate(self.TaskGroups):
            if not tg.Name:
                errs.append(f"Job task group {idx + 1} missing name")
            elif tg.Name in taskGroups:
                errs.append(f"Job task group {tg.Name} defined multiple times")
            taskGroups[tg.Name] = idx
            if self.Type == JobTypeSystem and tg.Count != 1:
                errs.append(
                    f"Job task group {tg.Name} should have a count of 1, got {tg.Count}"
                )
        for tg in self.TaskGroups:
            for e in tg.validate():
                errs.append(f"Task group {tg.Name} validation failed: {e}")
        if self.Periodic is not None and self.Periodic.Enabled:
            if self.Type != JobTypeBatch:
                errs.append(f"Periodic can only be used with {JobTypeBatch!r} scheduler")
            errs.extend(self.Periodic.validate())
        return errs

    def lookup_task_group(self, name: str) -> Optional[TaskGroup]:
        for tg in self.TaskGroups:
            if tg.Name == name:
                return tg
        return None

    def is_periodic(self) -> bool:
        return self.Periodic is not None and self.Periodic.Enabled


# ---------------------------------------------------------------------------
# Nodes
# ---------------------------------------------------------------------------


@dataclass
class Node:
    """A client machine in the cluster (reference: structs.go:551-688)."""

    ID: str = ""
    Datacenter: str = ""
    Name: str = ""
    HTTPAddr: str = ""
    Attributes: Dict[str, str] = field(default_factory=dict)
    Resources: Optional[Resources] = None
    Reserved: Optional[Resources] = None
    Links: Dict[str, str] = field(default_factory=dict)
    Meta: Dict[str, str] = field(default_factory=dict)
    NodeClass: str = ""
    ComputedClass: str = ""
    Drain: bool = False
    Status: str = ""
    StatusDescription: str = ""
    StatusUpdatedAt: int = 0
    CreateIndex: int = 0
    ModifyIndex: int = 0

    def copy(self) -> "Node":
        return copy.deepcopy(self)

    def terminal_status(self) -> bool:
        return self.Status == NodeStatusDown

    def stub(self) -> "NodeListStub":
        return NodeListStub(
            ID=self.ID,
            Datacenter=self.Datacenter,
            Name=self.Name,
            NodeClass=self.NodeClass,
            Drain=self.Drain,
            Status=self.Status,
            StatusDescription=self.StatusDescription,
            CreateIndex=self.CreateIndex,
            ModifyIndex=self.ModifyIndex,
        )


@dataclass
class NodeListStub:
    ID: str = ""
    Datacenter: str = ""
    Name: str = ""
    NodeClass: str = ""
    Drain: bool = False
    Status: str = ""
    StatusDescription: str = ""
    CreateIndex: int = 0
    ModifyIndex: int = 0


def should_drain_node(status: str) -> bool:
    """(reference: structs.go:ShouldDrainNode)"""
    if status in (NodeStatusInit, NodeStatusReady):
        return False
    return status == NodeStatusDown


def valid_node_status(status: str) -> bool:
    return status in (NodeStatusInit, NodeStatusReady, NodeStatusDown)


# ---------------------------------------------------------------------------
# Allocations
# ---------------------------------------------------------------------------


@dataclass
class AllocMetric:
    """Per-placement scheduling telemetry (reference: structs.go:2497-2595)."""

    NodesEvaluated: int = 0
    NodesFiltered: int = 0
    NodesAvailable: Dict[str, int] = field(default_factory=dict)
    ClassFiltered: Dict[str, int] = field(default_factory=dict)
    ConstraintFiltered: Dict[str, int] = field(default_factory=dict)
    NodesExhausted: int = 0
    ClassExhausted: Dict[str, int] = field(default_factory=dict)
    DimensionExhausted: Dict[str, int] = field(default_factory=dict)
    Scores: Dict[str, float] = field(default_factory=dict)
    AllocationTime: int = 0  # ns
    CoalescedFailures: int = 0

    def copy(self) -> "AllocMetric":
        # Hot path: every placed allocation snapshots the eval's metrics
        # (reference: alloc.Metrics). Values are scalars; dict() per field
        # replaces deepcopy's reflective walk.
        return AllocMetric(
            NodesEvaluated=self.NodesEvaluated,
            NodesFiltered=self.NodesFiltered,
            NodesAvailable=dict(self.NodesAvailable),
            ClassFiltered=dict(self.ClassFiltered),
            ConstraintFiltered=dict(self.ConstraintFiltered),
            NodesExhausted=self.NodesExhausted,
            ClassExhausted=dict(self.ClassExhausted),
            DimensionExhausted=dict(self.DimensionExhausted),
            Scores=dict(self.Scores),
            AllocationTime=self.AllocationTime,
            CoalescedFailures=self.CoalescedFailures)

    def evaluate_node(self) -> None:
        self.NodesEvaluated += 1

    def filter_node(self, node: Optional[Node], constraint: str) -> None:
        self.NodesFiltered += 1
        if node is not None and node.NodeClass:
            self.ClassFiltered[node.NodeClass] = self.ClassFiltered.get(node.NodeClass, 0) + 1
        if constraint:
            self.ConstraintFiltered[constraint] = self.ConstraintFiltered.get(constraint, 0) + 1

    def exhausted_node(self, node: Optional[Node], dimension: str) -> None:
        self.NodesExhausted += 1
        if node is not None and node.NodeClass:
            self.ClassExhausted[node.NodeClass] = self.ClassExhausted.get(node.NodeClass, 0) + 1
        if dimension:
            self.DimensionExhausted[dimension] = self.DimensionExhausted.get(dimension, 0) + 1

    def score_node(self, node: Node, name: str, score: float) -> None:
        key = f"{node.ID}.{name}"
        self.Scores[key] = score


@dataclass
class Allocation:
    """A placement of a task group on a node (reference: structs.go:2308-2495)."""

    ID: str = ""
    EvalID: str = ""
    Name: str = ""
    NodeID: str = ""
    JobID: str = ""
    Job: Optional[Job] = None
    TaskGroup: str = ""
    Resources: Optional[Resources] = None
    TaskResources: Dict[str, Resources] = field(default_factory=dict)
    Services: Dict[str, str] = field(default_factory=dict)
    Metrics: Optional[AllocMetric] = None
    DesiredStatus: str = ""
    DesiredDescription: str = ""
    ClientStatus: str = ""
    ClientDescription: str = ""
    TaskStates: Dict[str, TaskState] = field(default_factory=dict)
    CreateIndex: int = 0
    ModifyIndex: int = 0
    AllocModifyIndex: int = 0

    def copy(self) -> "Allocation":
        return copy.deepcopy(self)

    def terminal_status(self) -> bool:
        """Terminal by desired or client state (reference: structs.go:2377-2394)."""
        if self.DesiredStatus in (AllocDesiredStatusStop, AllocDesiredStatusEvict,
                                  AllocDesiredStatusFailed):
            return True
        return self.ClientStatus in (AllocClientStatusComplete, AllocClientStatusFailed)

    def ran_successfully(self) -> bool:
        if not self.TaskStates:
            return False
        return all(ts.successful() for ts in self.TaskStates.values())

    def stub(self) -> "AllocListStub":
        return AllocListStub(
            ID=self.ID,
            EvalID=self.EvalID,
            Name=self.Name,
            NodeID=self.NodeID,
            JobID=self.JobID,
            TaskGroup=self.TaskGroup,
            DesiredStatus=self.DesiredStatus,
            DesiredDescription=self.DesiredDescription,
            ClientStatus=self.ClientStatus,
            ClientDescription=self.ClientDescription,
            TaskStates=self.TaskStates,
            CreateIndex=self.CreateIndex,
            ModifyIndex=self.ModifyIndex,
        )


@dataclass
class AllocListStub:
    ID: str = ""
    EvalID: str = ""
    Name: str = ""
    NodeID: str = ""
    JobID: str = ""
    TaskGroup: str = ""
    DesiredStatus: str = ""
    DesiredDescription: str = ""
    ClientStatus: str = ""
    ClientDescription: str = ""
    TaskStates: Dict[str, TaskState] = field(default_factory=dict)
    CreateIndex: int = 0
    ModifyIndex: int = 0


@dataclass
class JobListStub:
    ID: str = ""
    ParentID: str = ""
    Name: str = ""
    Type: str = ""
    Priority: int = 0
    Status: str = ""
    StatusDescription: str = ""
    CreateIndex: int = 0
    ModifyIndex: int = 0


def job_stub(j: Job) -> JobListStub:
    return JobListStub(
        ID=j.ID, ParentID=j.ParentID, Name=j.Name, Type=j.Type, Priority=j.Priority,
        Status=j.Status, StatusDescription=j.StatusDescription,
        CreateIndex=j.CreateIndex, ModifyIndex=j.ModifyIndex,
    )


# ---------------------------------------------------------------------------
# Evaluations and plans
# ---------------------------------------------------------------------------


@dataclass
class Evaluation:
    """A scheduling work item (reference: structs.go:2642-2843)."""

    ID: str = ""
    Priority: int = 0
    Type: str = ""
    TriggeredBy: str = ""
    JobID: str = ""
    # Home region of the eval's job (federation): stamped at creation
    # when ServerConfig.federation is enabled so the broker can route
    # region-aware; "" (the default, and the only value when federation
    # is off) means region-agnostic — pre-federation behavior.
    Region: str = ""
    JobModifyIndex: int = 0
    NodeID: str = ""
    NodeModifyIndex: int = 0
    Status: str = ""
    StatusDescription: str = ""
    Wait: int = 0  # ns
    NextEval: str = ""
    PreviousEval: str = ""
    BlockedEval: str = ""
    FailedTGAllocs: Dict[str, AllocMetric] = field(default_factory=dict)
    ClassEligibility: Dict[str, bool] = field(default_factory=dict)
    EscapedComputedClass: bool = False
    AnnotatePlan: bool = False
    SnapshotIndex: int = 0
    CreateIndex: int = 0
    ModifyIndex: int = 0

    def copy(self) -> "Evaluation":
        # Hot path: every eval completion copies the eval for its status
        # write. All fields are scalars except the two dicts; deepcopy's
        # reflective walk costs ~100x this.
        out = replace(self)
        out.FailedTGAllocs = {k: v.copy()
                              for k, v in self.FailedTGAllocs.items()}
        out.ClassEligibility = dict(self.ClassEligibility)
        return out

    def terminal_status(self) -> bool:
        return self.Status in (EvalStatusComplete, EvalStatusFailed, EvalStatusCancelled)

    def should_enqueue(self) -> bool:
        if self.Status == EvalStatusPending:
            return True
        if self.Status in (EvalStatusComplete, EvalStatusFailed, EvalStatusBlocked,
                           EvalStatusCancelled):
            return False
        raise ValueError(f"unhandled evaluation ({self.ID}) status {self.Status}")

    def should_block(self) -> bool:
        if self.Status == EvalStatusBlocked:
            return True
        if self.Status in (EvalStatusComplete, EvalStatusFailed, EvalStatusPending,
                           EvalStatusCancelled):
            return False
        raise ValueError(f"unhandled evaluation ({self.ID}) status {self.Status}")

    def make_plan(self, job: Optional[Job], copy_job: bool = True) -> "Plan":
        """(reference: structs.go:2795-2808). copy_job=False lets a hot
        caller alias the snapshot's committed Job — safe because jobs are
        value-frozen in the state store (updates replace the object) and the
        plan only reads it; the reference aliases the pointer the same way."""
        plan = Plan(EvalID=self.ID, Priority=self.Priority)
        if job is not None:
            plan.Job = job.copy() if copy_job else job
            plan.AllAtOnce = job.AllAtOnce
        return plan

    def next_rolling_eval(self, wait: int) -> "Evaluation":
        """(reference: structs.go:2810-2825)"""
        return Evaluation(
            ID=generate_uuid(),
            Priority=self.Priority,
            Type=self.Type,
            TriggeredBy=EvalTriggerRollingUpdate,
            JobID=self.JobID,
            Region=self.Region,
            JobModifyIndex=self.JobModifyIndex,
            Status=EvalStatusPending,
            Wait=wait,
            PreviousEval=self.ID,
        )

    def create_blocked_eval(self, class_eligibility: Dict[str, bool],
                            escaped: bool) -> "Evaluation":
        """(reference: structs.go:2827-2843)"""
        return Evaluation(
            ID=generate_uuid(),
            Priority=self.Priority,
            Type=self.Type,
            TriggeredBy=self.TriggeredBy,
            JobID=self.JobID,
            Region=self.Region,
            JobModifyIndex=self.JobModifyIndex,
            Status=EvalStatusBlocked,
            PreviousEval=self.ID,
            ClassEligibility=class_eligibility,
            EscapedComputedClass=escaped,
        )


def stamp_alloc(template: Dict[str, Any], alloc_id: str, name: str,
                node_id: str) -> Allocation:
    """One placement as an object: a clone of its task group's template
    Allocation (`template` is that object's __dict__: EvalID, JobID,
    TaskGroup, the shared TaskResources, Metrics and resource vector) with
    its own identity and fresh client-mutable containers. The 20-field
    dataclass constructor never runs."""
    alloc = object.__new__(Allocation)
    alloc.__dict__ = dict(template)
    alloc.ID = alloc_id
    alloc.Name = name
    alloc.NodeID = node_id
    alloc.Services = {}
    alloc.TaskStates = {}
    return alloc


def column_list(values) -> list:
    """A column of a columnar raft entry as a plain list: a list as it is
    (no copy), an ndarray by one `tolist` (plain ints and strings, never
    numpy scalars). The entry's consumers (FSM, store, event builder) take
    the arrays DevRaft hands on and the lists a decoded log entry holds."""
    if isinstance(values, list):
        return values
    tolist = getattr(values, "tolist", None)
    return tolist() if tolist is not None else list(values)


class _PlacedColumns:
    """What a plan's ColumnarPlacements and every copy made of it share:
    the columns, and the per-node lists once any reader had them built."""

    __slots__ = ("cols", "by_node", "position")

    def __init__(self, cols):
        self.cols = cols
        self.by_node: Optional[Dict[str, List[Allocation]]] = None
        self.position: Optional[Dict[str, int]] = None  # node id -> index

    def index(self, node_id) -> Optional[int]:
        position = self.position
        if position is None:
            position = self.position = {
                nid: i for i, nid in enumerate(self.cols.node_ids)}
        return position.get(node_id)


_STAMP_LOCK = threading.Lock()  # the applier and a worker may ask at once


class ColumnarPlacements(dict):
    """`Plan.NodeAllocation` of a plan whose placements exist as columns
    only: a view over the plan's full-coverage batch descriptor (`cols`:
    `node_ids` one a placed node in row order, `starts` the range of each
    node's placements in the per-placement columns `alloc_ids`,
    `alloc_names`, `alloc_tg`, and `templates`, one Allocation a task
    group: scheduler.system_sweep.SweepBatch).

    Keys, length, membership, truth and the counts are answered from the
    columns. Every other read or write first builds the per-node lists of
    Allocation, once for the plan and all copies of this view (a
    PlanResult's wholesale admit holds one), and from then on this is the
    plain dict it subclasses. A reader that knows nothing of columns
    therefore sees what it always saw, and pays for the objects it asks
    for."""

    __slots__ = ("_shared", "_live")

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._shared: Optional[_PlacedColumns] = None
        self._live = False  # still answering from the columns

    @classmethod
    def over(cls, cols) -> "ColumnarPlacements":
        self = cls()
        self._shared = _PlacedColumns(cols)
        self._live = True
        return self

    @property
    def objects_built(self) -> bool:
        """Whether any reader made this plan's placements into objects."""
        return self._shared is None or self._shared.by_node is not None

    def _build(self) -> None:
        if not self._live:
            return
        shared = self._shared
        with _STAMP_LOCK:
            if shared.by_node is None:
                cols = shared.cols
                ids, names, tg = (cols.alloc_ids, cols.alloc_names,
                                  cols.alloc_tg)
                templates = [t.__dict__ for t in cols.templates]
                bounds = [int(b) for b in cols.starts]
                shared.by_node = {
                    nid: [stamp_alloc(templates[tg[p]], ids[p], names[p],
                                      nid)
                          for p in range(bounds[i], bounds[i + 1])]
                    for i, nid in enumerate(cols.node_ids)}
            if self._live:
                dict.update(self, shared.by_node)
                self._live = False

    # ---- answered from the columns
    def __len__(self) -> int:
        if self._live:
            return len(self._shared.cols.node_ids)
        return dict.__len__(self)

    def __iter__(self):
        if self._live:
            return iter(self._shared.cols.node_ids)
        return dict.__iter__(self)

    def __contains__(self, node_id) -> bool:
        if self._live:
            return self._shared.index(node_id) is not None
        return dict.__contains__(self, node_id)

    def keys(self):
        return KeysView(self) if self._live else dict.keys(self)

    def count(self, node_id: str) -> int:
        """Placements on one node."""
        if not self._live:
            return len(dict.get(self, node_id, ()))
        i = self._shared.index(node_id)
        if i is None:
            return 0
        starts = self._shared.cols.starts
        return int(starts[i + 1] - starts[i])

    def total(self) -> int:
        """Placements on all nodes."""
        if self._live:
            return len(self._shared.cols.alloc_ids)
        return sum(map(len, dict.values(self)))

    def copy(self) -> Dict[str, List[Allocation]]:
        """A verdict's own mapping of the same placements: columns while
        nobody asked for objects, the same lists once somebody did."""
        if not self._live:
            return dict.copy(self)
        other = ColumnarPlacements()
        other._shared = self._shared
        other._live = True
        return other

    def __repr__(self) -> str:
        if self._live:
            return (f"ColumnarPlacements(nodes={len(self)}, "
                    f"placements={self.total()})")
        return dict.__repr__(self)

    def __reduce__(self):
        self._build()
        return (dict, (dict(self),))  # copies and pickles are plain dicts


def _building(name: str):
    plain = getattr(dict, name)

    def method(self, *args, **kwargs):
        self._build()
        return plain(self, *args, **kwargs)

    method.__name__ = name
    return method


for _name in ("__getitem__", "__setitem__", "__delitem__", "__eq__", "__ne__",
              "__or__", "__ror__", "__ior__", "__reversed__", "get", "items",
              "values", "setdefault", "pop", "popitem", "update", "clear"):
    setattr(ColumnarPlacements, _name, _building(_name))


def columns_only(node_allocation: Dict[str, List[Allocation]]) -> bool:
    """Whether these placements exist as columns alone: no reader has had
    them built into objects (yet)."""
    return (isinstance(node_allocation, ColumnarPlacements)
            and not node_allocation.objects_built)


def placed_count(node_allocation: Dict[str, List[Allocation]]) -> int:
    """Placements a plan or a verdict carries, without building objects
    that exist as columns only."""
    if isinstance(node_allocation, ColumnarPlacements):
        return node_allocation.total()
    return sum(map(len, node_allocation.values()))


@dataclass
class Plan:
    """Scheduler output submitted to the plan applier (reference: structs.go:2845-2928)."""

    EvalID: str = ""
    EvalToken: str = ""
    Priority: int = 0
    AllAtOnce: bool = False
    Job: Optional[Job] = None
    NodeUpdate: Dict[str, List[Allocation]] = field(default_factory=dict)
    NodeAllocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    Annotations: Optional["PlanAnnotations"] = None

    def append_update(self, alloc: Allocation, status: str, desc: str) -> None:
        # Strip the embedded job from a SHALLOW copy before deep-copying:
        # the plan carries the job once, so deep-copying it per evicted alloc
        # would dominate plan construction — and the shallow copy means the
        # store-shared alloc object is never mutated (other threads read it).
        shallow = copy.copy(alloc)
        shallow.Job = None
        new_alloc = copy.deepcopy(shallow)
        new_alloc.DesiredStatus = status
        new_alloc.DesiredDescription = desc
        self.NodeUpdate.setdefault(alloc.NodeID, []).append(new_alloc)

    def pop_update(self, alloc: Allocation) -> None:
        existing = self.NodeUpdate.get(alloc.NodeID, [])
        if existing and existing[-1].ID == alloc.ID:
            existing.pop()
            if not existing:
                self.NodeUpdate.pop(alloc.NodeID, None)

    def append_alloc(self, alloc: Allocation) -> None:
        self.NodeAllocation.setdefault(alloc.NodeID, []).append(alloc)

    def is_no_op(self) -> bool:
        return not self.NodeUpdate and not self.NodeAllocation


@dataclass
class PlanResult:
    """Plan applier's verdict (reference: structs.go:2931-2966)."""

    NodeUpdate: Dict[str, List[Allocation]] = field(default_factory=dict)
    NodeAllocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    RefreshIndex: int = 0
    AllocIndex: int = 0

    def full_commit(self, plan: Plan) -> tuple[bool, int, int]:
        expected = placed_count(plan.NodeAllocation)
        actual = placed_count(self.NodeAllocation)
        return expected == actual, expected, actual


@dataclass
class DesiredUpdates:
    Ignore: int = 0
    Place: int = 0
    Migrate: int = 0
    Stop: int = 0
    InPlaceUpdate: int = 0
    DestructiveUpdate: int = 0


@dataclass
class PlanAnnotations:
    """Dry-run plan annotations (reference: structs.go:2970-2984)."""

    DesiredTGUpdates: Dict[str, DesiredUpdates] = field(default_factory=dict)


@dataclass
class JobPlanResponse:
    """Dry-run plan reply (reference: structs.go JobPlanResponse,
    job_endpoint.go:422-526)."""

    Diff: Optional[Any] = None  # structs.diff.JobDiff
    Annotations: Optional["PlanAnnotations"] = None
    FailedTGAllocs: Dict[str, "AllocMetric"] = field(default_factory=dict)
    NextPeriodicLaunch: float = 0.0
    JobModifyIndex: int = 0
    CreatedEvals: List["Evaluation"] = field(default_factory=list)


@dataclass
class PeriodicLaunch:
    """Last launch time of a periodic job (reference: structs.go:1270-1278)."""

    ID: str = ""
    Launch: float = 0.0  # unix seconds
    CreateIndex: int = 0
    ModifyIndex: int = 0
