"""Benchmark harness: runs one cell of ``BENCHMARK.json`` and prints its
result line.

Everything that belongs to one configuration, traffic mix or metric is found
by name:

- a cell's configuration is the file that ``BENCHMARK.json`` names for it;
  its ``kind`` names the module ``bench/kinds/<kind>.py`` (what its traffic
  files must give, set-up, window, correctness check) and its plain
  reference ``bench/reference/<kind>.py``;
- its traffic mix is ``bench/traffic/<traffic>.json``, parameters for the
  general generators of ``bench/traffic/generators.py``;
- an end-to-end metric is read by ``bench/metrics/<name>.py`` and a
  per-layer metric by ``bench/layers/<name>.py``: each has
  ``read(record) -> float | None`` over the window's record.

A run: the device check (a TPU with the cell's chips, or exit without a
result), the program's persistent compilation cache inside the checkout,
the kind's set-up (instances, warm-up of every shape the traffic uses),
the measured window of ``--seconds``, the peak device memory, then the
comparison with the plain reference.  The last stdout line is the result
JSON; the numbers compared, each beside its limit, are the last stderr lines
and the result's last key.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by path (metric files carry dots in
    their names, so they are not importable by module name)."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str, spec: dict):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{', '.join(sorted(cells))}")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        with open(ROOT / configs[self.workload["config"]]["file"]) as f:
            self.config = json.load(f)
        with open(BENCH / "traffic" / f"{self.workload['traffic']}.json") as f:
            self.traffic = json.load(f)
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]

    def readers(self, trace: bool) -> dict:
        """``{metric name: (unit, reader module)}`` for this run: the cell's
        end-to-end metrics, or with ``trace`` its per-layer metrics."""
        metrics, folder = ((self.per_layer, "layers") if trace
                           else (self.end_to_end, "metrics"))
        return {m["name"]: (m["unit"],
                            load_module(BENCH / folder / f"{m['name']}.py"))
                for m in metrics}

    def kind(self):
        return importlib.import_module(f"bench.kinds.{self.config['kind']}")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    """The devices JAX found, as the result line names them.  Raises
    :class:`NoAccelerator` unless they are at least ``chips`` TPUs: there is
    no CPU path."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoAccelerator(f"JAX found no TPU ({info['count']} "
                            f"{info['platform']} device(s)); the benchmark "
                            "measures the chip and has no CPU path")
    if info["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{info['count']}")
    return info


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the cell's chips, where the
    backend reports it."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts the programs that reach the backend, from the listener of
    ``chip_smoke.py``'s ``_count_compiles``.  In this JAX a persistent-cache
    hit also reports a backend compile; it reports its retrieval too, so
    ``loads`` counts those and ``count - loads`` the programs compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.count = 0
        self.loads = 0
        self.seconds = 0.0

    def _on_event(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration
        elif event == self.LOAD:
            self.loads += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


class GcPauses:
    """Python's garbage-collection pauses while it is entered: a pause
    stalls the host path like any other host work, so the window's pauses
    are printed beside its timings."""

    def __init__(self):
        self.count = self.full = 0
        self.seconds = self.longest = 0.0
        self._t = 0.0

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        self.count += 1
        self.full += info["generation"] == 2
        self.seconds += dt
        self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict = None) -> dict:
    """Set up, measure and check one run of ``cell``; return the result.

    ``t_start`` is the process start on the ``time.perf_counter`` clock.
    ``device`` is the caller's :func:`device_info`; ``None`` skips the chip
    (the harness's own tests drive a run on the CPU that way)."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.core import fused

    log(f"[setup] compile cache: {fused.enable_persistent_cache()}")
    with CompileCounter() as compiles:
        return _run(cell, seed, seconds, trace, t_start, device, compiles)


def _run(cell, seed, seconds, trace, t_start, device, compiles):
    from bench import trace as tracing

    kind = cell.kind()
    readers = cell.readers(trace)
    state = kind.setup(cell, seed)
    c0, cs0 = compiles.count, compiles.seconds
    tracer = tracing.Tracer() if trace else None
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    log(f"[setup] {setup_s!r} s, {compiles.count - compiles.loads} programs "
        f"compiled and {compiles.loads} loaded from the cache "
        f"({compiles.seconds!r} s in the backend)")
    with GcPauses() as pauses:
        record = kind.window(state, seconds, tracer)
    record["setup_s"] = setup_s
    record["compiles_in_window"] = compiles.count - c0
    log(f"[window] {record['window_s']!r} s, programs compiled or loaded "
        f"inside the window: {record['compiles_in_window']} "
        f"({compiles.seconds - cs0!r} s); garbage collections: "
        f"{pauses.count} ({pauses.full} full), {pauses.seconds!r} s, "
        f"longest {pauses.longest!r} s")
    if device is not None:
        device = dict(device,
                      memory_peak_bytes=memory_peak_bytes(cell.chips))
    if tracer is not None:
        record["trace"] = tracer.reduce()
    checks = kind.check(state, record)
    del state
    metrics = {}
    for name, (unit, mod) in readers.items():
        value = mod.read(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": record["attempted"], "failed": record["failed"],
           "metrics": metrics}
    if device is not None:
        out["device"] = device
        if trace:
            tr = record["trace"] or {}
            out["device"]["busy_s"] = tr.get("busy_s")
            out["device"]["window_s"] = tr.get("window_s")
    if trace and record["trace"]:
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["checked"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    return out


def print_result(out: dict) -> None:
    for name, c in out["checked"].items():
        print(f"checked {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out, allow_nan=False, default=_json_default), flush=True)


def _json_default(x):
    if hasattr(x, "item"):
        return x.item()
    raise TypeError(f"not JSON: {x!r}")


