"""What one run of a cell carries between its parts, and how the harness
finds a cell's files by the names in ``BENCHMARK.json``:

  configuration   the ``file`` its entry names (a JSON object), built and
                  referenced through bench/harness/archs/<model_type>.py
  traffic mix     bench/traffic/<traffic>.json, run by the loop its
                  ``"loop"`` key names (bench/harness/<loop>.py)
  per-layer metric  bench/metrics/<name>.py, whose ``read(outcome)`` returns
                  a number or None (nothing to read: the metric is left out)
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Run:
    workload: str
    seed: int
    seconds: float
    trace: bool
    cell: dict
    model: dict                 # the configuration file
    traffic: dict               # the traffic file
    chips: int
    rehearse: bool = False      # CPU, configuration's "rehearsal" sizes
    control: bool = False       # run the cell's control (not timed runs)
    fault: str | None = None    # plant a fault in the reference put in the
                                # program's place ("half", "regrow_random")
    t_start: float = 0.0


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a loop hands back: end-to-end numbers, the counts the
    per-layer readers use, the comparisons that decide ``correct``."""
    metrics: dict[str, float]
    checks: list[Check]
    attempted: int
    failed: int
    counters: dict = dataclasses.field(default_factory=dict)
    trace: object = None            # harness.trace.Trace of the window
    memory_peak_bytes: int | None = None
    model: dict = dataclasses.field(default_factory=dict)
    traffic: dict = dataclasses.field(default_factory=dict)
    peaks: object = None            # harness.peaks.Peaks (None on the CPU)
    compiles_in_window: int = 0

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _override(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_override(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def resolve(spec: dict, workload: str, root: pathlib.Path = ROOT,
            rehearse: bool = False) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of a workload; under ``rehearse`` the
    files' ``"rehearsal"`` sizes replace theirs."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    model = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    if rehearse:
        model = _override(model, model.get("rehearsal", {}))
        traffic = _override(traffic, traffic.get("rehearsal", {}))
    return cell, model, traffic


def loop(traffic: dict):
    return importlib.import_module(f"harness.{traffic['loop']}")


def load_reader(name: str, root: pathlib.Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache while
    ``active`` (both stand between a dispatch and the device)."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        self.names: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self._add(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self._add("cache hit")

    def _add(self, name):
        self.count += 1
        self.names[name] = self.names.get(name, 0) + 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()


def quantile(values, q: float) -> float:
    """The q-quantile by the nearest-rank rule (values need not be
    sorted)."""
    import math
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return float(v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))])
