"""Name every blocking device -> host read a process makes, by the
frame that made it.

Put this directory on ``PYTHONPATH`` of any command that runs the engine
(the cell benchmark's serving child inherits it):

    PYTHONPATH=tools/sync_audit python3 benchmark/run.py --workload \\
        tpcds_sf1.wide_agg --seed 7 --seconds 8 --trace 0
    python3 tools/sync_audit/sitecustomize.py chiprun_out/sync_audit.*.jsonl

Every host read of a ``jax.Array`` — ``int(x)``, ``np.asarray(x)``,
``jax.device_get(x)``, ``x.tolist()``, a ``repr`` — passes
``ArrayImpl._value``; this wraps it and, where the value is not on the
host yet (a real transfer, which waits for the program that makes it),
appends one line to ``chiprun_out/sync_audit.<pid>.jsonl``: the
innermost frame under ``auron_tpu/`` outside ``obs/profile.py``, and
whether the read was made inside the sync helpers of ``obs/profile.py``
(``timed_get`` and ``row_count``: ``_get``) or the result fetch of
``columnar/arrow_bridge.py``. With ``SYNC_AUDIT_GUARD=log`` in the
environment it also sets ``jax_transfer_guard_device_to_host`` to that
level, so the backend's own record of the implicit ones is in the
process's stderr (left out of a timed run: it logs every one). Run as a
script it folds such files into one table: reads by site, helper or not.
"""

import json
import os
import sys

_HELPERS = (("obs/profile.py", "_get"),
            ("columnar/arrow_bridge.py", None))


def _site(frame):
    """(innermost auron_tpu frame, inside a helper?) of a read."""
    site = None
    helper = False
    while frame is not None:
        name = frame.f_code.co_filename.replace(os.sep, "/")
        at = name.rfind("/auron_tpu/")
        if at >= 0:
            rel = name[at + len("/auron_tpu/"):]
            func = frame.f_code.co_name
            if any(rel == f and fn in (None, func) for f, fn in _HELPERS):
                helper = True
            elif site is None and rel != "obs/profile.py":
                site = f"{rel}:{frame.f_lineno} {func}"
        frame = frame.f_back
    return site, helper


def install(out_path: str) -> None:
    import jax
    from jax._src import array
    guard = os.environ.get("SYNC_AUDIT_GUARD")
    if guard:
        jax.config.update("jax_transfer_guard_device_to_host", guard)
    inner = array.ArrayImpl._value.fget
    out = open(out_path, "a", buffering=1)

    def note(frame):
        site, helper = _site(frame)
        if site is not None or helper:
            out.write(json.dumps({"site": site, "helper": helper}) + "\n")

    def _value(self):
        if self._npy_value is None:
            note(sys._getframe(1))
        return inner(self)

    array.ArrayImpl._value = property(_value)

    # the CPU backend hands numpy a buffer and never passes _value
    import numpy as np

    def through_numpy(fn):
        def wrapped(a, *args, **kwargs):
            if isinstance(a, array.ArrayImpl) and a._npy_value is None \
                    and next(iter(a.devices())).platform == "cpu":
                note(sys._getframe(1))
            return fn(a, *args, **kwargs)
        return wrapped

    np.asarray = through_numpy(np.asarray)
    np.array = through_numpy(np.array)


def fold(paths) -> dict:
    table: dict = {}
    for path in paths:
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                key = (rec["site"] or "(helper's own)", rec["helper"])
                table[key] = table.get(key, 0) + 1
    return table


if __name__ == "__main__":
    for (site, helper), n in sorted(fold(sys.argv[1:]).items(),
                                    key=lambda kv: (kv[0][1], kv[0][0])):
        print(f"{'helper ' if helper else 'IMPLICIT'} {n:7d}  {site}")
else:
    os.makedirs("chiprun_out", exist_ok=True)
    install(os.path.join("chiprun_out", f"sync_audit.{os.getpid()}.jsonl"))
