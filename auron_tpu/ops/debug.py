"""Debug operator: logs batches flowing through (reference:
datafusion-ext-plans/src/debug_exec.rs)."""

from __future__ import annotations

import logging
from typing import Iterator

from auron_tpu.columnar.arrow_bridge import to_arrow
from auron_tpu.columnar.schema import Schema
from auron_tpu.obs import profile as _profile
from auron_tpu.ops.base import ExecContext, PhysicalOp, count_output

logger = logging.getLogger("auron_tpu.debug")


class DebugOp(PhysicalOp):
    name = "debug"

    def __init__(self, child: PhysicalOp, label: str = "",
                 max_preview_rows: int = 5):
        self.child = child
        self.label = label
        self.max_preview_rows = max_preview_rows

    @property
    def children(self):
        return [self.child]

    def schema(self) -> Schema:
        return self.child.schema()

    def execute(self, partition: int, ctx: ExecContext) -> Iterator:
        metrics = ctx.metrics_for(self)
        schema = self.child.schema()

        def stream():
            enabled = logger.isEnabledFor(logging.INFO)
            for i, batch in enumerate(self.child.execute(partition, ctx)):
                if enabled:
                    n = _profile.row_count(batch)
                    preview = ""
                    if n and self.max_preview_rows:
                        rb = to_arrow(batch, schema)
                        preview = rb.slice(0, self.max_preview_rows).to_pydict()
                    logger.info("[debug%s] partition=%d batch=%d rows=%d "
                                "capacity=%d %s",
                                f" {self.label}" if self.label else "",
                                partition, i, n, batch.capacity, preview)
                yield batch

        return count_output(stream(), metrics, timed=True)

    def __repr__(self):
        return f"DebugOp[{self.label}]"
