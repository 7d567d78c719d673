"""A subplan's one result, read by every parent it has (ir/reuse.py).

The planner plans a shared subtree ONCE and hangs a ``SubplanReadOp``
where each of its parents had it. The first handle the planner makes
owns the producer as its ``child``, so the post-planning passes, the
metric tree and EXPLAIN meet the producer once; the others are leaves.
Whichever handle a task pulls first runs the producer for that partition
to its end and keeps its output batches on the device; every handle then
yields those batches, and the last one to finish a partition lets them
go (as does a cancelled task, and the end of any task with its operator
tree).

The batches belong to all the readers: ``owns_output`` is False, so no
consumer donates one to XLA while another parent has yet to read it.
"""

from __future__ import annotations

import threading
from typing import Iterator, Optional

from auron_tpu.columnar.batch import DeviceBatch
from auron_tpu.columnar.schema import Schema
from auron_tpu.obs import trace as _trace
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output


class _Held:
    """One partition's result and the handles yet to finish reading it."""

    __slots__ = ("lock", "batches", "readers_left")

    def __init__(self, readers: int):
        self.lock = threading.Lock()
        self.batches: Optional[list] = None
        self.readers_left = readers


class SharedSubplan:
    """What the handles of one subplan share. Not an operator: the tree
    passes follow ``PhysicalOp`` attributes and must not come here."""

    def __init__(self):
        #: the handle whose ``child`` is the producer (the passes rewrite
        #: that attribute in place, so it is read at execute time)
        self.owner: Optional["SubplanReadOp"] = None
        #: handles planned over this subplan: known when planning ends
        self.consumers = 0
        self._lock = threading.Lock()
        self._held: dict[int, _Held] = {}

    def held_partitions(self) -> list:
        """Partitions whose result is on the device now."""
        with self._lock:
            return sorted(p for p, h in self._held.items()
                          if h.batches is not None)

    def read(self, partition: int, ctx: ExecContext) -> Iterator[DeviceBatch]:
        with self._lock:
            held = self._held.get(partition)
            if held is None:
                held = self._held[partition] = _Held(self.consumers)
        try:
            with held.lock:
                hit = held.batches is not None
                if not hit:
                    # to its end, whoever asks first: a parent that stops
                    # early (a limit) must not leave the others a
                    # half-read producer
                    batches = []
                    for b in self.owner.child.execute(partition, ctx):
                        ctx.checkpoint("subplan.produce")
                        batches.append(b)
                    held.batches = batches
            if hit:
                _trace.count("subplan_reuse_hits")
            yield from held.batches
        finally:
            with self._lock:
                held.readers_left -= 1
                # a stopped task's other handles will not come; a failed
                # producer leaves nothing worth finding on a retry
                if (held.readers_left <= 0 or held.batches is None
                        or ctx.should_stop) \
                        and self._held.get(partition) is held:
                    del self._held[partition]


class SubplanReadOp(PhysicalOp):
    """One parent's handle over a shared subplan's result."""

    name = "subplan_read"
    #: the held batches are every reader's (module docstring)
    owns_output = False

    def __init__(self, shared: SharedSubplan,
                 child: Optional[PhysicalOp] = None):
        self.shared = shared
        shared.consumers += 1
        if child is not None:
            self.child = child
            shared.owner = self

    @property
    def children(self):
        return [self.child] if self.shared.owner is self else []

    def schema(self) -> Schema:
        return self.shared.owner.child.schema()

    def execute(self, partition: int,
                ctx: ExecContext) -> Iterator[DeviceBatch]:
        return count_output(self.shared.read(partition, ctx),
                            ctx.metrics_for(self), timed=True)

    def __repr__(self):
        return f"SubplanReadOp[{self.shared.consumers} readers]"
