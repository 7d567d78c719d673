"""Exchange layer: the live share of what the collective moves — the sum
over the window's tasks of counts.mesh_bytes (rows received x row width)
over the sum of counts.mesh_slot_bytes (the padded n_dev^2 x quota slot
buffers the all_to_all carries whatever is in them). None where no
frame has both counts, or no slot was allocated."""

import ledgerlib


def read(ctx):
    live = slots = 0
    for task in ctx["tasks"]:
        a = ledgerlib.field(task, "counts", "mesh_bytes")
        b = ledgerlib.field(task, "counts", "mesh_slot_bytes")
        if a is not None and b is not None:
            live += a
            slots += b
    return live / slots if slots else None
