"""The one process of a run that holds the chip.

The benchmark's wrapper round the system under test: it finds the
devices (and fails where there is no TPU, unless ``JAX_PLATFORMS=cpu``
was given explicitly — the rehearsal path, whose result line says
``cpu``), applies the configuration's engine settings, binds the compile
cache through ``utils/xla_cache.py``, starts ``AuronServer`` and then
answers the parent's commands, one JSON object a line on stdin, one JSON
reply a line on the reply pipe:

  build   plan bytes for a list of tasks (a ``Session`` is needed for
          them, and a ``Session`` touches the backend, so they are built
          here and handed to the parent as a file)
  trace_start / trace_stop   bracket a slice with the jax profiler
  stats   memory peak, compile and kernel-registry counts
  exit    leave at once: the window is over, its answers are with the
          parent, and a task cut in flight is not waited for

Stdout and stderr of this process are the parent's log file.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))           # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))   # the checkout

from harness import cell as cellmod                 # noqa: E402


def find_devices(chips: int) -> dict:
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    rehearsal = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    if platform != "tpu" and not rehearsal:
        raise SystemExit(f"no TPU: jax found {len(devs)} {platform} "
                         "device(s); a benchmark run needs the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, jax found "
                         f"{len(devs)} {platform} device(s)")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    keeps no such count, as the CPU's does not)."""
    import jax
    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def build_tasks(session, spec: dict, out_path: str) -> int:
    """Write ``u32 len | TaskDefinition bytes`` for each task of
    ``spec["tasks"]`` ([plan, [fact files]]) in order."""
    plans = {name: cellmod.load_module("plans", name)
             for name in {t[0] for t in spec["tasks"]}}
    with open(out_path, "wb") as f:
        for plan, files in spec["tasks"]:
            df = plans[plan].build(session, spec["tables"], files,
                                   spec["scan_partitions"])
            if df.num_partitions != 1:
                raise SystemExit(
                    f"plan {plan} ends in {df.num_partitions} partitions; "
                    "a task is one TaskDefinition")
            blob = df.task_bytes(0)
            f.write(struct.pack("<I", len(blob)))
            f.write(blob)
    return len(spec["tasks"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--reply-fd", type=int, required=True)
    args = ap.parse_args(argv)
    reply = os.fdopen(args.reply_fd, "w", buffering=1)

    def say(obj: dict) -> None:
        reply.write(json.dumps(obj) + "\n")
        reply.flush()

    t0 = time.monotonic()
    device = find_devices(args.chips)
    t_dev = time.monotonic() - t0

    from auron_tpu import config as cfg
    from auron_tpu.frontend.session import Session
    from auron_tpu.kernels import registry as kregistry
    from auron_tpu.runtime.serving import AuronServer
    from auron_tpu.utils import compile_stats, xla_cache

    conf = cfg.get_config()
    for key, value in cellmod.load_json(args.config)["engine"].items():
        conf.set(key, value)
    compile_stats.install()
    cache_dir = xla_cache.bind()
    entries_before = xla_cache.entries(cache_dir)
    session = Session()
    server = AuronServer()
    server.serve_background()
    say({"ready": True, "device": device, "port": server.address[1],
         "host": server.address[0], "device_init_s": t_dev,
         "cache_dir": cache_dir, "cache_entries": entries_before})

    import jax
    for line in sys.stdin:
        cmd = json.loads(line)
        what = cmd["cmd"]
        if what == "build":
            say({"built": build_tasks(session, cmd, cmd["out"])})
        elif what == "trace_start":
            opts = jax.profiler.ProfileOptions()
            # the python tracer doubles the host's work per call; the
            # device planes and XLA's own host events are what is read
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
            say({"tracing": True, "t": time.monotonic()})
        elif what == "trace_stop":
            jax.profiler.stop_trace()
            say({"tracing": False, "t": time.monotonic()})
        elif what == "stats":
            snap = compile_stats.snapshot()
            say({"memory_peak_bytes": memory_peak_bytes(),
                 "xla_programs": snap.count,
                 "xla_compile_s": snap.seconds,
                 "persistent_cache": compile_stats.persistent_cache(),
                 "cache_entries": xla_cache.entries(cache_dir),
                 "kernels": kregistry.snapshot()})
        elif what == "exit":
            break
        else:
            say({"error": f"unknown command {what!r}"})
    say({"bye": True})
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
