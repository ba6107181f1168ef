"""One run of one benchmark cell:

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds`` and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``; the numbers compared for ``correct``
come last, under ``checks``, and again as the last lines on standard error.

A run on anything but the TPUs the cell asks for exits non-zero and prints
no result. ``--rehearse`` (tests only) runs the same path on the CPU at the
files' ``"rehearsal"`` sizes and prints counts, never device metrics;
``--control`` reads the cell's control and ``--fault <kind>`` a planted
fault (see PERF.md); neither is part of a timed run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from harness import core


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--control", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--fault", choices=("half", "regrow_random"),
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def _environment():
    """Fixed kernel blocks and a compile cache that later runs find."""
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(core.ROOT / ".jax_cache"
                                             / "no-autotune.json")
    sys.path.insert(0, str(core.ROOT / "src"))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(core.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def main(argv=None, t_start=None) -> int:
    t_start = core.now() if t_start is None else t_start
    args = parse_args(argv)
    spec = core.load_spec()
    cell, model, traffic = core.resolve(spec, args.workload,
                                        rehearse=args.rehearse)
    jax = _environment()
    devices = jax.devices()
    dev = devices[0]
    chips = int(cell["chips"])
    core.log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)}")
    if args.rehearse:
        if dev.platform != "cpu":
            core.log("[device] --rehearse runs on the CPU only")
            return 2
    elif dev.platform != "tpu" or len(devices) < chips:
        core.log(f"[device] FAILED: this cell runs on {chips} TPU chip(s); "
                 f"found {len(devices)} {dev.platform} device(s)")
        return 2
    peaks = None
    if not args.rehearse:
        from harness import peaks as P
        peaks = P.peaks_for(dev.device_kind)
    run = core.Run(workload=args.workload, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace), cell=cell,
                   model=model, traffic=traffic, chips=chips,
                   rehearse=args.rehearse, control=args.control,
                   fault=args.fault,
                   t_start=t_start)
    out = core.loop(traffic).run(run)
    out.peaks = peaks
    core.log(f"[window] programs compiled in the window: "
             f"{out.compiles_in_window}")
    result = _result(spec, run, out, dev, len(devices))
    for c in out.checks:
        core.log(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
                 f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


def _result(spec, run, out, dev, count) -> dict:
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": out.memory_peak_bytes}
    if run.rehearse:
        metrics = {"rehearsal_counts": {k: v for k, v in out.counters.items()
                                        if isinstance(v, (int, float))}}
    elif run.control or run.fault:
        metrics = {}
    elif run.trace:
        metrics = per_layer(spec, run.workload, out)
    else:
        metrics = {m["name"]: {"value": out.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]
                   if run.workload in m.get("workloads", [run.workload])}
    if run.trace and out.trace is not None and not run.rehearse:
        from harness import trace as TR
        device["busy_s"] = TR.busy_seconds(out.trace)
        device["window_s"] = out.trace.window_s
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if run.trace and out.trace is not None and not run.rehearse:
        from harness import trace as TR
        result["breakdown"] = TR.breakdown(out.trace)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


def per_layer(spec, workload, out, root=core.ROOT) -> dict:
    """Each per-layer metric of this cell whose reader finds something."""
    metrics = {}
    for m in spec["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = core.load_reader(m["name"], root).read(out)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics
