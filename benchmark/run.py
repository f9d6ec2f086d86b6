"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` — one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one cell, one configuration, one driver, one
model family or one per-layer metric is a file of its own, found by the
name ``BENCHMARK.json`` or the cell's file gives:

    workloads/<cell>.json          config, traffic, chips, why, limits
    traffic/<traffic>.json         the mix's parameters and its driver
    configs/<config>.json          published sizes, reduced, assumed
    drivers/<driver>.py            run(ctx) -> record
    models/<family>.py             program builder, yardstick, reference
    layer_metrics/<metric>.py      read(record, name) -> number or None

The last line of standard output is the result object; earlier lines
(one JSON object each) say where set-up went, what compiled, the loss
of every dispatch and which roofline bounds which kernel.  Off a TPU
the run fails; ``--rehearse`` (tiny widths, CPU allowed, every device
metric ``null``) exists to test control flow and never passes for a
result.
"""

import time
PROCESS_START = time.perf_counter()

import argparse        # noqa: E402
import importlib       # noqa: E402
import importlib.util  # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import sys             # noqa: E402
import threading       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SCRATCH = os.path.join(ROOT, ".bench_scratch")


def emit(**fields):
    """One JSON object a line on stdout, flushed."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_by_path(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, whatever characters
    the name holds (a metric's name may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark.%s.%s" % (kind, name.replace(".", "_").replace(
            "-", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_reader(metric):
    """A per-layer metric's reader: ``layer_metrics/<name>.py``, else
    the file named by the part before the first dot (a quantity split
    by what it moves: ``device_idle_pct.train``)."""
    return load_by_path("layer_metrics", metric) or \
        load_by_path("layer_metrics", metric.split(".")[0])


class CompileMeter(object):
    """Sums what JAX reports through ``jax.monitoring``: seconds in
    backend compiles, persistent-cache hits and misses (a copy of
    ``chip_smoke.CompileMeter``).  ``take()`` returns the figures since
    the last call."""

    def __init__(self):
        import jax.monitoring as monitoring
        self._lock = threading.Lock()
        self._zero()
        monitoring.register_event_duration_secs_listener(
            self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _zero(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, seconds, **_kw):
        if event.endswith("backend_compile_duration"):
            with self._lock:
                self.compile_s += seconds
                self.compiles += 1

    def _on_event(self, event, **_kw):
        with self._lock:
            if event.endswith("/cache_hits"):
                self.cache_hits += 1
            elif event.endswith("/cache_misses"):
                self.cache_misses += 1

    def take(self):
        with self._lock:
            out = {"compile_s": self.compile_s,
                   "programs_compiled": self.compiles,
                   "cache_hits": self.cache_hits,
                   "cache_misses": self.cache_misses}
            self._zero()
        return out


class Context(object):
    """What a driver is handed."""

    def __init__(self, args, bench, workload, mix, config, family, peaks,
                 chips):
        self.chips = chips
        self.meter = CompileMeter()
        self.args = args
        self.bench = bench
        self.workload = workload
        self.mix = mix
        self.config = config
        self.family = family
        self.peaks = peaks          # None in a rehearsal
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.emit = emit
        self.scratch = SCRATCH
        self.process_start = PROCESS_START

    def memory_peak_bytes(self):
        """``peak_bytes_in_use`` of the fullest chip the cell uses, or
        None where the backend keeps no such count (the CPU)."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()[:self.chips]]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def since_start(self):
        return time.perf_counter() - self.process_start

    def limits(self):
        """The cell's limits (set from chip readings at the cell's own
        size); a rehearsal has its own, since a tiny model on the CPU
        rounds differently."""
        if self.rehearse:
            return self.workload["rehearsal_limits"]
        return self.workload["limits"]

    def traffic(self):
        """The cell's traffic parameters, the rehearsal's laid over
        them where this is one."""
        traffic = dict(self.mix["traffic"])
        if self.rehearse:
            traffic.update(self.mix.get("rehearsal", {}))
        return traffic


def device_entry(chips, rehearse):
    """The device as JAX reports it; fails off a TPU, on an unknown
    kind, or with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    entry = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    peaks = load_json(HERE, "peaks.json")
    if rehearse:
        return entry, None
    if entry["platform"] != "tpu":
        raise SystemExit("benchmark: JAX runs on %r, not on a TPU; no "
                         "result (use --rehearse for control flow)" %
                         (entry,))
    if entry["kind"] not in peaks:
        raise SystemExit("benchmark: device kind %r is not in "
                         "benchmark/peaks.json" % entry["kind"])
    if entry["count"] < chips:
        raise SystemExit("benchmark: the cell asks for %d chips, JAX "
                         "sees %d" % (chips, entry["count"]))
    entry["count"] = chips
    return entry, peaks[entry["kind"]]


def metric_lines(bench, group, cell):
    """The metrics of ``group`` that this cell reports: those that list
    it, and of those that list no cell, every end-to-end metric and
    every per-layer metric that moves an end-to-end metric of this
    cell."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]
    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    if group == "end_to_end":
        return end_to_end
    mine = {m["name"] for m in end_to_end}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def prepare(args):
    """Loads the cell's files and modules and checks the device:
    everything before the driver runs.  ``benchmark/control.py`` starts
    the same way."""
    sys.path.insert(0, ROOT)
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    workload = load_json(HERE, "workloads", args.workload + ".json")
    # A cell that is not (or not yet) in BENCHMARK.json can be tried from
    # its file alone; its metrics are then nobody's.
    cell = cells.get(args.workload, workload)
    config = load_json(HERE, "configs", cell["config"] + ".json")
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")

    # The program under test must be there: a directory that holds only
    # the benchmark fails here, before anything is printed.
    from veles_tpu.backends import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    # Keep the small programs too (norms, samples, the reference's
    # helpers): by default JAX persists only what took a second to
    # compile, and a run makes dozens of them at half a second each.
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device, peaks = device_entry(cell["chips"], args.rehearse)
    family = importlib.import_module(
        "benchmark.models." + config["family"])
    driver = importlib.import_module(
        "benchmark.drivers." + mix["driver"])
    ctx = Context(args, bench, workload, mix, config, family, peaks,
                  cell["chips"])
    emit(phase="start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace, device=device,
         rehearse=args.rehearse, compile_cache_dir=cache_dir)
    return ctx, driver, device


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny widths, CPU allowed, device metrics "
                             "null: control flow only, never a result")
    args = parser.parse_args(argv)
    ctx, driver, device = prepare(args)
    bench = ctx.bench

    record = driver.run(ctx)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    listed = args.workload in {w["name"] for w in bench["workloads"]}
    if not listed:
        # a cell tried from its files alone: BENCHMARK.json names no
        # metric for it, so the result carries none; what it measured
        # goes on an earlier line
        emit(phase="unlisted", end_to_end=None if args.rehearse
             else record["end_to_end"])
    for line in metric_lines(bench, group, args.workload) if listed \
            else ():
        name = line["name"]
        if group == "end_to_end":
            value = record["end_to_end"].get(name)
        else:
            reader = find_reader(name)
            if reader is None:
                raise SystemExit("benchmark: no reader for %r" % name)
            value = reader.read(record, name)
        if value is None:
            continue
        if args.rehearse and line["source"] != "program_counter":
            value = None
        metrics[name] = {"value": value, "unit": line["unit"]}
    device["memory_peak_bytes"] = None if args.rehearse else \
        record.get("memory_peak_bytes")
    result = {"correct": bool(record["correct"]),
              "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    trace = record.get("trace")
    if args.trace and trace and not args.rehearse:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    checks = record["checks"]
    result["compared"] = checks
    for c in checks:
        print("compared %s = %r (limit %r)%s" %
              (c["name"], c["value"], c["limit"],
               "" if c["ok"] else "  <-- NOT correct"),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
