"""The table of peaks, keyed by ``device_kind``. No reader uses it yet:
no operator or kernel carries a stable name in the trace, so no roofline
share is claimed. It ships so that the tracing PR only adds readers."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]
