"""Watch items: fine-grained notification keys for blocking queries.

(reference: nomad/watch/watch.go, nomad/state/notify.py analog)
A watch Item identifies one thing to watch: a table, a specific object, or an
object scoped to a relation (allocs of a node, evals of a job, ...).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Set


class Item:
    """One watchable key. Set exactly one field (or one scoped pair).

    Accepted fields: alloc, alloc_eval, alloc_job, alloc_node, eval, job,
    node, service_name, table. Stored as a single (field, value) key with a
    precomputed hash: every state-store commit builds and hashes dozens of
    Items (one per written object plus relation keys), so construction and
    hashing are on the FSM apply hot path — a 9-field frozen dataclass costs
    ~4x as much per commit for the same set semantics."""

    __slots__ = ("_key", "_hash")

    FIELDS = frozenset((
        "alloc", "alloc_eval", "alloc_job", "alloc_node", "eval", "job",
        "node", "service_name", "table"))

    def __init__(self, **kw):
        if len(kw) == 1:
            self._key = next(iter(kw.items()))
            if self._key[0] not in Item.FIELDS:
                raise TypeError(f"unknown watch field: {self._key[0]}")
        else:  # scoped pair (rare): canonical order keeps equality stable
            for k in kw:
                if k not in Item.FIELDS:
                    raise TypeError(f"unknown watch field: {k}")
            self._key = tuple(sorted(kw.items()))
        self._hash = hash(self._key)

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return isinstance(other, Item) and self._key == other._key

    def __repr__(self) -> str:  # debugging aid only
        return f"Item({self._key!r})"


class Items(set):
    """A set of watch Items (reference: watch.Items)."""

    def __init__(self, items: Iterable[Item] = ()):  # noqa: D401
        super().__init__(items)

    def add_item(self, item: Item) -> None:
        self.add(item)


class NotifyGroup:
    """Fan-out notifications to registered waiters (reference: state/notify.go)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._waiters: Dict[Item, Set[threading.Event]] = {}

    def watch(self, items: Iterable[Item], event: threading.Event) -> None:
        with self._lock:
            for item in items:
                self._waiters.setdefault(item, set()).add(event)

    def stop_watch(self, items: Iterable[Item], event: threading.Event) -> None:
        with self._lock:
            for item in items:
                waiters = self._waiters.get(item)
                if waiters is not None:
                    waiters.discard(event)
                    if not waiters:
                        self._waiters.pop(item, None)

    def notify(self, items: Iterable[Item],
               scoped: "Dict[str, Iterable[str]]" = None) -> None:
        """Wake waiters of `items`, plus — via `scoped` — waiters whose
        single-field key falls inside a bulk key column ({field: values}).

        The scoped form exists for columnar batch commits: a 10k-alloc
        sweep touches 10k (alloc, alloc_node) keys, and building+hashing
        an Item per key would put an O(batch) loop back on the commit
        path. Intersecting against the REGISTERED waiters instead costs
        O(waiters), and waiters are bounded by connected blocking queries,
        not by batch size. A field's column is hashed into a set by the
        first waiter registered on that field, so a commit nobody watches
        by node or by allocation hashes nothing."""
        with self._lock:
            fired: Set[threading.Event] = set()
            for item in items:
                for ev in self._waiters.get(item, ()):
                    fired.add(ev)
            if scoped:
                sets: Dict[str, Set[str]] = {}
                for item, evs in self._waiters.items():
                    field = item._key[0]
                    if not isinstance(field, str) or field not in scoped:
                        continue
                    values = sets.get(field)
                    if values is None:
                        values = sets[field] = set(scoped[field])
                    if item._key[1] in values:
                        fired.update(evs)
        for ev in fired:
            ev.set()

    def notify_all(self) -> None:
        """Wake EVERY registered waiter. For whole-store events — a
        snapshot restore swaps every table, so any blocked query's
        object may have changed regardless of which keys it watches.
        O(waiters), and restores are rare."""
        with self._lock:
            fired = {ev for evs in self._waiters.values() for ev in evs}
        for ev in fired:
            ev.set()
