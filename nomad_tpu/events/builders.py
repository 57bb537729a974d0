"""Event builders: one per FSM MessageType (reference: the per-type
event constructors in nomad/state/events.go, keyed off the raft message
the entry carried).

Builders run inside ``FSM.apply`` AFTER the handler committed, on every
replica, so they are deterministic functions of (payload, post-apply
state) — identical event streams on leader and followers, which is what
makes failover resume gapless. They derive from the raft PAYLOAD (the
same dict-or-object shapes the handlers accept) rather than re-reading
whole objects back, and they publish SUMMARIES, not full object dumps:
an event identifies the transition and the ids/statuses a consumer folds
into shadow state; full objects stay one API read away.

The columnar rule (the reason this module exists at all): an
``ApplySweepBatch`` entry — one raft entry for a 10k-alloc sweep —
publishes ONE ``AllocationBatch`` event carrying the row/count
descriptor. No per-alloc materialization happens here; per-alloc
fan-out is opt-in at read time (broker.expand_batch).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

from nomad_tpu.structs import column_list

from .schema import new_event

__all__ = ["build_events"]


def _f(obj: Any, name: str, default: Any = "") -> Any:
    """Field access across the two payload shapes (wire dicts / dev-mode
    objects), mirroring the handlers' own tolerance."""
    if isinstance(obj, dict):
        return obj.get(name, default)
    return getattr(obj, name, default)


def _alloc_event(etype: str, alloc: Any, job: Any = None) -> Dict[str, Any]:
    job_id = _f(alloc, "JobID") or (_f(job, "ID") if job is not None else "")
    return new_event("Alloc", etype, _f(alloc, "ID"), {
        "ID": _f(alloc, "ID"),
        "Name": _f(alloc, "Name"),
        "JobID": job_id,
        "EvalID": _f(alloc, "EvalID"),
        "NodeID": _f(alloc, "NodeID"),
        "DesiredStatus": _f(alloc, "DesiredStatus"),
        "ClientStatus": _f(alloc, "ClientStatus"),
    })


def _node_register(fsm, req):
    node = req["Node"]
    return [new_event("Node", "NodeRegistered", _f(node, "ID"), {
        "ID": _f(node, "ID"),
        "Name": _f(node, "Name"),
        "Status": _f(node, "Status"),
        "Datacenter": _f(node, "Datacenter"),
        "NodeClass": _f(node, "NodeClass"),
    })]


def _node_deregister(fsm, req):
    return [new_event("Node", "NodeDeregistered", req["NodeID"],
                      {"ID": req["NodeID"]})]


def _node_status(fsm, req):
    return [new_event("Node", "NodeStatusUpdated", req["NodeID"],
                      {"ID": req["NodeID"], "Status": req["Status"]})]


def _node_drain(fsm, req):
    return [new_event("Node", "NodeDrainUpdated", req["NodeID"],
                      {"ID": req["NodeID"], "Drain": bool(req["Drain"])})]


def _job_register(fsm, req):
    job = req["Job"]
    return [new_event("Job", "JobRegistered", _f(job, "ID"), {
        "ID": _f(job, "ID"),
        "Name": _f(job, "Name"),
        "Type": _f(job, "Type"),
        "Priority": _f(job, "Priority", 0),
    })]


def _job_deregister(fsm, req):
    return [new_event("Job", "JobDeregistered", req["JobID"],
                      {"ID": req["JobID"]})]


def _eval_update(fsm, req):
    return [new_event("Eval", "EvalUpdated", _f(ev, "ID"), {
        "ID": _f(ev, "ID"),
        "JobID": _f(ev, "JobID"),
        "Status": _f(ev, "Status"),
        "Type": _f(ev, "Type"),
        "TriggeredBy": _f(ev, "TriggeredBy"),
    }) for ev in req["Evals"]]


def _eval_delete(fsm, req):
    events = [new_event("Eval", "EvalDeleted", eval_id, {"ID": eval_id})
              for eval_id in req.get("Evals", ())]
    events.extend(new_event("Alloc", "AllocDeleted", alloc_id,
                            {"ID": alloc_id})
                  for alloc_id in req.get("Allocs", ()))
    return events


def _alloc_update(fsm, req):
    groups = req.get("Batch")
    if groups is None:
        groups = [req]
    events = []
    for group in groups:
        job = group.get("Job")
        events.extend(_alloc_event("AllocUpdated", a, job)
                      for a in group["Alloc"])
    return events


def _alloc_client_update(fsm, req):
    events = []
    for a in req["Alloc"]:
        # Mirror the handler: updates for already-GC'd allocs were
        # dropped before the write, so they publish nothing. The status
        # comes from the STORE read-back — the handler merges client
        # fields, and the event must carry what committed.
        updated = fsm.state.alloc_by_id(_f(a, "ID"))
        if updated is None:
            continue
        events.append(new_event("Alloc", "AllocClientUpdated", updated.ID, {
            "ID": updated.ID,
            "ClientStatus": updated.ClientStatus,
            "DesiredStatus": updated.DesiredStatus,
            "Terminal": updated.terminal_status(),
        }))
    return events


def _sweep_batch(fsm, req):
    groups = req.get("Batch")
    if groups is None:
        groups = [req]
    events = []
    for group in groups:
        job = group.get("Job")
        sweep = group.get("Sweep")
        if sweep is None:
            events.extend(_alloc_event("AllocUpdated", a, job)
                          for a in group.get("Alloc", ()))
            continue
        # Exact-path evictions ride the sweep group ahead of its
        # placements; they are per-object updates and publish as such.
        events.extend(_alloc_event("AllocUpdated", a, job)
                      for a in group.get("Updates", ()))
        templates = sweep["Templates"]
        alloc_ids = column_list(sweep["AllocIDs"])
        events.append(new_event(
            "AllocationBatch", "AllocationBatchCommitted",
            _f(templates[0], "JobID"), {
                "JobID": _f(templates[0], "JobID"),
                "EvalID": _f(templates[0], "EvalID"),
                "Kind": sweep.get("Kind", "system"),
                "Count": len(alloc_ids),
                "AllocIDs": alloc_ids,
                "Names": column_list(sweep["Names"]),
                "RowNodeIDs": column_list(sweep["RowNodeIDs"]),
                "Counts": column_list(sweep["Counts"]),
            }))
    return events


def _periodic_launch(fsm, req):
    job_id = _f(req["Launch"], "ID")
    return [new_event("Job", "PeriodicLaunchUpserted", job_id,
                      {"JobID": job_id})]


def _periodic_launch_delete(fsm, req):
    return [new_event("Job", "PeriodicLaunchDeleted", req["JobID"],
                      {"JobID": req["JobID"]})]


def _service_sync(fsm, req):
    events = [new_event("Service", "ServiceRegistered", _f(reg, "ID"), {
        "ID": _f(reg, "ID"),
        "ServiceName": _f(reg, "ServiceName"),
        "JobID": _f(reg, "JobID"),
        "AllocID": _f(reg, "AllocID"),
        "NodeID": _f(reg, "NodeID"),
    }) for reg in req.get("Upserts", ())]
    events.extend(new_event("Service", "ServiceDeregistered", reg_id,
                            {"ID": reg_id})
                  for reg_id in req.get("Deletes", ()))
    return events


# MessageType.value -> builder. Keyed by int so this module never imports
# server.fsm (which imports the broker through the events package — the
# dependency points one way only).
_BUILDERS: Dict[int, Callable[[Any, Dict[str, Any]],
                              List[Dict[str, Any]]]] = {
    0: _node_register,
    1: _node_deregister,
    2: _node_status,
    3: _node_drain,
    4: _job_register,
    5: _job_deregister,
    6: _eval_update,
    7: _eval_delete,
    8: _alloc_update,
    9: _alloc_client_update,
    10: _periodic_launch,
    11: _periodic_launch_delete,
    12: _service_sync,
    13: _sweep_batch,
}


def build_events(fsm, msg_type: int,
                 payload: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The FSM's one publish hook per MessageType: dispatch to the
    builder for this entry's type. Unknown types publish nothing (a
    newer leader's entry replaying on an older replica must not wedge
    the sequencer)."""
    builder = _BUILDERS.get(int(msg_type))
    if builder is None:
        return []
    return builder(fsm, payload)
