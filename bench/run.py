"""Benchmark of the Laplacian solver on the chip: one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``: the graph and the solver's options) and a
traffic mix (``bench/traffic/<traffic>.json``, read by ``bench/load.py``).
``bench/workloads/<name>.json`` holds the limits of the cell's correctness
check. Each metric is read by ``bench/metrics/<metric>.py``.

The run builds everything from ``--seed``, warms up (set-up, reported as
``setup_s``), then runs requests in a closed loop with one client until the
first request that ends after ``--seconds`` of request time. With
``--trace 1`` the window runs under the profiler and the cell's per-layer
metrics are reported; with ``--trace 0`` its end-to-end metrics. After the
window every answer is compared with the float64 reference
(``bench/reference.py``).

It refuses to run without a TPU, or with fewer chips than the cell asks
for. JAX's persistent compilation cache is the program's own
(``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.jax_cache`` in the
checkout), and holds every program however short its compile; traces and
libtpu's logs go to ``.bench_cache``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each compared number
with its limit, also printed as the last lines of standard error.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """A cell's entries in ``BENCHMARK.json`` and its data files."""

    def __init__(self, name: str):
        spec = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        self.config = load_json(BENCH, "configs",
                                self.entry["config"] + ".json")
        self.traffic = load_json(BENCH, "traffic",
                                 self.entry["traffic"] + ".json")
        self.limits = load_json(BENCH, "workloads", name + ".json")["limits"]
        self.end_to_end = [m for m in spec["end_to_end"] if self._has(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if self._has(m) and m["moves"] in moved]

    def _has(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])


def module(*parts):
    """Import ``bench/<parts>.py`` by its path (``trace`` would otherwise
    find the standard library's module of that name)."""
    name = "bench_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts[:-1], parts[-1] + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, record: dict):
    """The ``read(record)`` of ``bench/metrics/<name>.py``, or where there is
    none, of the file named for the quantity before the first dot (one
    reader serves ``iter_ms.mesh`` and ``iter_ms.powerlaw``): a number, or
    None when the run holds nothing for it to read."""
    if not os.path.isfile(os.path.join(BENCH, "metrics", name + ".py")):
        name = name.split(".", 1)[0]
    return module("metrics", name).read(record)


class Compiles:
    """XLA programs made ready (``jax.monitoring`` events): ``compiled``
    counts every backend compile, a program loaded from the persistent
    cache included; ``hits`` counts those loads. Registered per instance,
    so each run in a process counts its own."""

    def __init__(self):
        import jax

        self.compiled = self.hits = 0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        jax.monitoring.register_event_listener(self._event)

    def _compiled(self, event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.names.append(fun_name)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def configure_jax() -> None:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` in the checkout: a fixed path), for
    every program however short its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    d = devices[0]
    info = dict(platform=d.platform, kind=d.device_kind, count=len(devices))
    if all(p is not None for p in peaks):
        info["memory_peak_bytes"] = int(max(peaks))
    return info


def compare(records: list, limits: dict) -> tuple[int, dict]:
    """Each answer against the float64 reference. Returns the number of
    failed requests and the compared numbers with their limits."""
    import reference

    laps: dict = {}
    worst = 0.0
    failed = 0
    for rec in records:
        g = rec["graph"]
        ok = rec["error"] is None and rec.get("status") == "converged"
        if rec["x"] is not None:
            lap = laps.get(id(g))
            if lap is None:
                lap = laps[id(g)] = reference.laplacian(*g)
            rec["rel_residual"] = reference.rel_residual(lap, rec["b"],
                                                         rec["x"])
            ok = ok and rec["rel_residual"] <= limits["max_rel_residual"]
        else:
            rec["rel_residual"] = float("inf")
        worst = max(worst, rec["rel_residual"])
        failed += not ok
    checks = dict(                      # JSON has no inf: a missing answer
        max_rel_residual=dict(value=min(worst, 1e300),      # reads 1e300
                              limit=limits["max_rel_residual"]),
        failed_requests=dict(value=failed, limit=0))
    return failed, checks


def start(cell: Cell, require_tpu: bool = True) -> list | None:
    """Configure JAX and return the cell's devices, or None where it may
    not run: no TPU, fewer chips than the cell asks for, or a device kind
    with no entry in ``peaks.json``."""
    for path in (os.path.join(ROOT, "src"), BENCH):
        if path not in sys.path:
            sys.path.insert(0, path)
    if "TPU_LOG_DIR" not in os.environ:      # libtpu logs in the checkout
        os.environ["TPU_LOG_DIR"] = os.path.join(CACHE, "tpu_logs")
        os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax

    configure_jax()
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            log(f"no TPU: JAX platform is {devices[0].platform!r}")
            return None
        if len(devices) < cell.chips:
            log(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
            return None
        peaks = load_json(BENCH, "peaks.json")["devices"]
        if devices[0].device_kind not in peaks:
            log(f"device kind {devices[0].device_kind!r} not in peaks.json")
            return None
    return devices[:cell.chips]


def window(loop, seconds: float, spans) -> tuple[list, float]:
    """Requests in a closed loop until the first that ends after
    ``seconds`` of request time; their records and the summed time."""
    records, clock = [], 0.0
    with spans("bench.window"):
        while clock < seconds:
            rec = loop.request(len(records))
            records.append(rec)
            clock += rec["t1"] - rec["t0"]
    return records, clock


def run(args, require_tpu: bool = True, cell: Cell | None = None
        ) -> dict | None:
    """One run of a cell; the result object, or None where it may not run.
    ``cell`` defaults to ``Cell(args.workload)``."""
    cell = cell or Cell(args.workload)
    devices = start(cell, require_tpu)
    if devices is None:
        return None
    import jax

    import load
    from repro.api import default_cache

    trace_mod = module("trace")

    compiles = Compiles()
    spans = load.Spans()
    t0 = time.perf_counter()
    loop = load.ClosedLoop(cell.config, cell.traffic, args.seed, spans)
    loop.prepare()
    setup_s = time.perf_counter() - t0
    setup_compiles = compiles.compiled
    log(f"{cell.name} seed={args.seed} setup_s={setup_s:.3f} "
        f"programs={setup_compiles} (cache hits {compiles.hits})")

    trace_dir = os.path.join(CACHE, "trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_opts())
    records, clock = window(loop, args.seconds, spans)
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.compiled - setup_compiles
    if window_compiles:
        log(f"compiled in the window: {compiles.names[setup_compiles:]}")
    device = device_info(devices)
    log("levels (kind, n, nnz): " + str(
        [(lv["kind"], lv["n"], lv.get("nnz"))
         for lv in loop.solver.stats()["levels"]]))
    loop.solver = None
    default_cache().clear()

    failed, checks = compare(records, cell.limits)
    for rec in records:
        log(f"request {rec['index']}: " + " ".join(
            f"{k}={rec[k]}" for k in ("solve_s", "iters", "status",
                                      "rel_residual", "error") if k in rec))
    log(f"window_s={clock:.3f} requests={len(records)} "
        f"window_programs={window_compiles}")
    record = dict(setup_s=setup_s, window_s=clock, requests=records,
                  window_compiles=window_compiles, trace=None)
    result = dict(correct=failed == 0 and bool(records),
                  attempted=len(records), failed=failed)
    if args.trace:
        record["trace"] = trace_mod.summarize(trace_dir)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = read_metric(m["name"], record)
        if value is not None:
            metrics[m["name"]] = dict(value=float(value), unit=m["unit"])
    result.update(metrics=metrics, device=device)
    if args.trace:
        result["breakdown"] = {k: record["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    result["checks"] = checks
    return result


def _profile_opts():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call Python events: they
    opts.enable_hlo_proto = False     # would slow the eager solve loop
    return opts


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        result = run(args, require_tpu)
    except (OSError, KeyError, ImportError) as e:
        log(f"cannot run {args.workload!r}: {e!r}")
        return 2
    if result is None:
        return 1
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
