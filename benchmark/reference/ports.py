"""Checks 9 and 10: the ports and the bandwidth of the configuration's
guarantees, recomputed from the state store's public reads alone.

guarantees.py holds capacity in CPU, memory and disk (check 2 stops at
disk) and knows no port. A configuration whose tasks ask for a network
(web-10k: 50 MBits and two dynamic ports a task) names this module under
its traffic's `extra_checks`, and `correct` then also means:

  9_ports_unique    on no node do two live allocations hold the same port
                    on the same IP;
  9_ports_reserved  none holds a port the node reserves on that IP;
  9_ports_offer     every offered network is a device of the node, with an
                    IP of that device's CIDR, at the MBits asked for;
  9_ports_labels    every task's offer answers its ask: one network an ask,
                    each reserved port under its label at its value, each
                    dynamic port once under its label with a value inside
                    the dynamic range, and nothing that was not asked for;
  10_bandwidth      no device's live MBits plus the MBits the node reserves
                    on it exceed the device's MBits.

Plain Python over nodes, jobs and allocations: dictionaries and sets of
(ip, port), the standard library's ipaddress for the CIDR. It shares
nothing with structs.network (NetworkIndex, Bitmap), the tensor path or the
scheduler: the dynamic range below is written out from the reference
(nomad/structs/network.go: MinDynamicPort, MaxDynamicPort), not imported.

Not repeated here: check 6 (guarantees.check) already holds the device
table's fifth column, the MBits in use per row, to the store within 1e-2;
with this configuration that column is for the first time not all reserve.
As everywhere in `correct`, only what holds in EVERY legal execution is
asked: a plan the applier refused for a doubled port, the fallback that
finished it, which ports were drawn and how often are counters.
"""

from __future__ import annotations

import ipaddress

MIN_DYNAMIC_PORT = 20000  # inclusive
MAX_DYNAMIC_PORT = 60000  # exclusive


def _asks(job, group_name):
    """task name -> the networks the job's task asks for."""
    group = next((g for g in job.TaskGroups if g.Name == group_name), None)
    if group is None:
        return {}
    return {t.Name: list(t.Resources.Networks) if t.Resources is not None
            else [] for t in group.Tasks}


def _answers(ask, offer):
    """Why `offer` does not answer `ask`, or None when it does."""
    reserved = sorted((p.Label, p.Value) for p in ask.ReservedPorts)
    if sorted((p.Label, p.Value) for p in offer.ReservedPorts) != reserved:
        return "reserved ports differ from the ask's"
    if sorted(p.Label for p in offer.DynamicPorts) \
            != sorted(p.Label for p in ask.DynamicPorts):
        return "dynamic port labels differ from the ask's"
    values = [p.Value for p in offer.DynamicPorts]
    if any(not MIN_DYNAMIC_PORT <= v < MAX_DYNAMIC_PORT for v in values):
        return "a dynamic port outside the dynamic range"
    held = values + [p.Value for p in offer.ReservedPorts]
    if len(set(held)) != len(held):
        return "one port offered twice"
    return None


def _network(memo, cidr):
    net = memo.get(cidr)
    if net is None:
        net = memo[cidr] = ipaddress.ip_network(cidr, strict=False)
    return net


def judge(reads, verdict):
    """Adds the failures of checks 9 and 10 to the verdict; returns the
    facts. reads: {"nodes", "jobs", "allocs"} lists from the store."""
    nodes = {n.ID: n for n in reads["nodes"]}
    jobs = {j.ID: j for j in reads["jobs"]}
    devices, reserved_ports, mbits = {}, {}, {}
    for nid, node in nodes.items():
        nets = node.Resources.Networks if node.Resources is not None else []
        devices[nid] = {n.Device: n for n in nets if n.Device}
        taken, used = set(), {}
        for n in (node.Reserved.Networks if node.Reserved is not None
                  else []):
            taken.update((n.IP, p.Value) for p in n.ReservedPorts)
            used[n.Device] = used.get(n.Device, 0) + n.MBits
        reserved_ports[nid], mbits[nid] = taken, used

    held = {nid: {} for nid in nodes}  # node -> (ip, port) -> alloc id
    asked, cidrs = {}, {}  # memos: (job, group) -> asks; CIDR -> network
    doubled, on_reserved, bad_offer, bad_labels = [], [], [], []
    ports_checked = 0
    with_ports = set()
    for a in reads["allocs"]:
        if a.terminal_status():
            continue
        node, job = nodes.get(a.NodeID), jobs.get(a.JobID)
        if node is None or job is None:
            continue  # check 3 names these
        asks = asked.get((a.JobID, a.TaskGroup))
        if asks is None:
            asks = asked[a.JobID, a.TaskGroup] = _asks(job, a.TaskGroup)
        for task, res in a.TaskResources.items():
            offers = list(res.Networks)
            ask = asks.get(task, [])
            if len(offers) != len(ask):
                bad_labels.append(f"{a.ID}/{task}: {len(offers)} networks "
                                  f"offered, {len(ask)} asked for")
                continue
            for want, offer in zip(ask, offers):
                why = _answers(want, offer)
                if why is not None:
                    bad_labels.append(f"{a.ID}/{task}: {why}")
                device = devices[a.NodeID].get(offer.Device)
                try:
                    inside = device is not None and ipaddress.ip_address(
                        offer.IP) in _network(cidrs, device.CIDR)
                except ValueError:
                    inside = False
                if not inside or offer.MBits != want.MBits:
                    bad_offer.append(
                        f"{a.ID}/{task}: {offer.Device or '?'} "
                        f"{offer.IP or '?'} at {offer.MBits} MBits on node "
                        f"{a.NodeID}")
                used = mbits[a.NodeID]
                used[offer.Device] = used.get(offer.Device, 0) + offer.MBits
                for port in list(offer.ReservedPorts) + list(
                        offer.DynamicPorts):
                    ports_checked += 1
                    with_ports.add(a.NodeID)
                    key = (offer.IP, port.Value)
                    if key in reserved_ports[a.NodeID]:
                        on_reserved.append(f"{a.ID}: {offer.IP}:"
                                           f"{port.Value} on {a.NodeID}")
                    holder = held[a.NodeID].get(key)
                    if holder is None:
                        held[a.NodeID][key] = a.ID
                    else:
                        doubled.append(f"{offer.IP}:{port.Value} on "
                                       f"{a.NodeID}: {holder} and {a.ID}")

    over, share = [], 0.0
    for nid, used in mbits.items():
        for device, total in used.items():
            have = devices[nid].get(device)
            if have is None or total > have.MBits:
                over.append(f"{nid}/{device or '?'}: {total} MBits of "
                            f"{have.MBits if have is not None else 0}")
            elif have.MBits:
                share = max(share, total / have.MBits)

    verdict.require("9_ports_unique", not doubled,
                    f"{len(doubled)} ports held twice on one IP of one node",
                    doubled)
    verdict.require("9_ports_reserved", not on_reserved,
                    f"{len(on_reserved)} ports held that the node reserves",
                    on_reserved)
    verdict.require("9_ports_offer", not bad_offer,
                    f"{len(bad_offer)} offers that are not the node's "
                    "device, an IP of its CIDR and the MBits asked for",
                    bad_offer)
    verdict.require("9_ports_labels", not bad_labels,
                    f"{len(bad_labels)} task offers that do not answer the "
                    "task's ask", bad_labels)
    verdict.require("10_bandwidth", not over,
                    f"{len(over)} devices hold more MBits than they have",
                    over)
    return {"ports_checked": ports_checked,
            "nodes_with_ports": len(with_ports),
            "max_mbits_share": share}


def check(dep, seed, verdict):
    """Adds the failures of checks 9 and 10 to the verdict; returns the
    facts. `seed` is the harness's: nothing here is drawn."""
    return judge(dep.reads(), verdict)
