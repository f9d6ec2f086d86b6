"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics
read: per device the busy union and the idle gaps of the traced window,
device time by operation name (self time: an operation that encloses
others, as a ``while`` does its body, is charged only what its children
leave), and the longest idle gaps laid against the benchmark's own host
annotations (``bench.*`` ``TraceAnnotation`` spans, on the same clock).

Reads the file with nothing but JAX (``jax.profiler.ProfileData``).
The reduction is pure (``reduce_planes`` takes plain lists), so the test
drives it on a small recorded trace kept as JSON under ``testdata/``.
"""

import glob
import gzip
import json
import os
import shutil
import threading

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
TOP = 10


def profile_options():
    """Host annotations on, the Python call tracer off (it writes an
    event for every Python call: hundreds of MB over a window)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


class TracedStretch(object):
    """The profiler over the first ``seconds`` of a measured window.  A
    driver calls ``tick(now)`` as its window goes (``now``: seconds since
    the window began) and ``finish(now)`` when it has closed; the trace
    is written out off the driver's thread (that takes a second) and
    ``finish`` returns its reduction, or None where tracing is off."""

    def __init__(self, directory, seconds, enabled):
        self.directory, self.seconds = directory, seconds
        self.enabled = enabled
        self.until = None
        self._stopper = None
        if enabled:
            import jax
            shutil.rmtree(directory, ignore_errors=True)
            jax.profiler.start_trace(directory,
                                     profiler_options=profile_options())

    def tick(self, now):
        if self.enabled and self._stopper is None and now >= self.seconds:
            import jax
            self.until = now
            self._stopper = threading.Thread(
                target=jax.profiler.stop_trace, name="bench-stop-trace")
            self._stopper.start()

    def finish(self, now):
        if not self.enabled:
            return None
        self.tick(float("inf"))
        self.until = min(self.until, now)
        self._stopper.join()
        reduced = reduce_dir(self.directory, self.until)
        shutil.rmtree(self.directory, ignore_errors=True)
        if reduced is not None:
            reduced["until"] = self.until
        return reduced


def earlier_line(trace):
    """What of a reduced trace goes on an earlier line of a run."""
    return {"window_s": trace["window_s"], "busy_s": trace["busy_s"],
            "device_seconds_by_kind": trace["kinds"],
            "programs": trace["programs"]}


def read_planes(path):
    """``{"devices": {plane: [(name, start_ns, dur_ns), ...]},
    "modules": {plane: [...]}, "host": [(name, start_ns, dur_ns), ...]}``
    from an xplane file: the operations line and the modules line (one
    event for each execution of a compiled program) of every device
    plane, and the benchmark's own annotations from the host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = devices if line.name == OPS_LINE else modules
                    into[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX))
    return {"devices": devices, "modules": modules, "host": host}


def union(intervals):
    """Merged (start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """Operation name -> seconds of SELF time.  Events on one line nest
    (a loop encloses its body); a child's time is taken off its
    parent's."""
    out = {}
    stack = []      # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + own
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


def cover_name(host, start, end):
    """What the benchmark's host side was doing over a device gap: the
    annotation that covers most of it, or ``between``."""
    best, best_cover = "between " + ANNOTATION_PREFIX + "* spans", 0.0
    for name, s, d in host:
        cover = min(end, s + d) - max(start, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_planes(planes, window_s=None):
    """The reduced trace.  The window is what the device planes span
    (first operation's start to last operation's end over all devices)
    unless ``window_s`` (the host's reading of the traced stretch) is
    longer: the device cannot have been busy outside its own events."""
    devices = planes["devices"]
    if not devices:
        return None
    first = min(e[1] for evs in devices.values() for e in evs)
    last = max(e[1] + e[2] for evs in devices.values() for e in evs)
    span_s = (last - first) / 1e9
    window = max(span_s, window_s or 0.0)
    busy, by_name, gaps = [], {}, []
    for name, events in devices.items():
        merged = union((s, s + d) for _n, s, d in events)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for op, seconds in self_times(events).items():
            by_name[op] = by_name.get(op, 0.0) + seconds / len(devices)
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    gap_names = {}
    for length, start, end in gaps[:200]:
        what = cover_name(planes["host"], start, end)
        gap_names[what] = gap_names.get(what, 0.0) + length / 1e9
    ops = sorted(short_names(by_name).items(), key=lambda kv: -kv[1])
    kinds = {}
    for name, seconds in by_name.items():
        kinds[kind_of(name)] = kinds.get(kind_of(name), 0.0) + seconds
    programs = {}
    for events in planes.get("modules", {}).values():
        for name, _start, dur in events:
            entry = programs.setdefault(name.split("(")[0], [0, 0.0])
            entry[0] += 1.0 / len(devices)
            entry[1] += dur / 1e9 / len(devices)
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "device_ops": [[k, v] for k, v in ops[:TOP]],
        "op_seconds": dict(ops),
        "kernel_seconds": {k: v for k, v in by_name.items()
                           if "custom-call(" in k},
        "programs": programs,
        "kinds": dict(sorted(kinds.items(), key=lambda kv: -kv[1])),
        "idle_gaps": [[k, v] for k, v in sorted(
            gap_names.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def kind_of(name):
    """A coarse kind for an operation's HLO line: what the table of
    ``where the time goes`` is grouped by while the program names
    nothing (no ``named_scope``, no kernel names)."""
    _head, _, rest = name.partition(" = ")
    if 'custom_call_target="tpu_custom_call"' in rest:
        return "pallas kernel (tpu_custom_call)"
    if "convolution" in name:
        return "matmul fusion (convolution)"
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all"):
        if " %s(" % op in rest or " %s-start(" % op in rest:
            return "collective"
    for op in ("copy", "dynamic-update-slice", "dynamic-slice", "gather",
               "scatter", "while", "conditional"):
        if " %s(" % op in rest:
            return op
    if "kind=" in rest:
        return "fusion " + rest.split("kind=", 1)[1].split(",")[0]
    return "other"


def short_names(by_name):
    """The trace names an operation by its whole HLO line.  Short form:
    the operation's name, what it is (fusion kind or custom-call
    target) and its result's type, at most 120 characters; seconds of
    operations that shorten alike are added."""
    out = {}
    for name, seconds in by_name.items():
        head, _, rest = name.partition(" = ")
        what = ""
        for mark in ("custom_call_target=", "kind="):
            if mark in rest:
                what = rest.split(mark, 1)[1].split(",")[0].strip('" ')
                break
        result = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
        short = " ".join(x for x in (head, what, result) if x)[:120]
        out[short] = out.get(short, 0.0) + seconds
    return out


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError("no .xplane.pb under %s" % trace_dir)
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir, window_s=None):
    """Reads the newest trace under ``trace_dir`` and reduces it.  With
    ``BENCH_TRACE_DUMP=<dir>`` in the environment a small recording of
    it (and a listing of every plane and line) is left there: how
    ``testdata/`` was made, and how to look at a trace by hand."""
    path = find_xplane(trace_dir)
    planes = read_planes(path)
    dump = os.environ.get("BENCH_TRACE_DUMP")
    if dump:
        os.makedirs(dump, exist_ok=True)
        dump_planes(planes, os.path.join(dump, "planes.json.gz"))
        with open(os.path.join(dump, "listing.txt"), "w") as f:
            f.write(list_planes(path))
    return reduce_planes(planes, window_s)


def list_planes(path):
    """Every plane and line of an xplane file with its event count and
    first events: what to read before writing code against a trace."""
    from jax.profiler import ProfileData
    lines = ["%s %d bytes" % (path, os.path.getsize(path))]
    for plane in ProfileData.from_file(path).planes:
        lines.append("PLANE %s" % plane.name)
        for line in plane.lines:
            events = list(line.events)
            lines.append("  LINE %s: %d events" % (line.name,
                                                   len(events)))
            for e in events[:6]:
                lines.append("    %s start %.0f dur %.0f" %
                             (e.name, e.start_ns, e.duration_ns))
    return "\n".join(lines) + "\n"


def dump_planes(planes, path, limit=4000):
    """A small recording of a trace for ``testdata/``: the first
    ``limit`` events of each device line and every host annotation."""
    small = {"devices": {k: v[:limit]
                         for k, v in planes["devices"].items()},
             "modules": {k: v[:limit]
                         for k, v in planes["modules"].items()},
             "host": planes["host"]}
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def load_planes(path):
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    for key in ("devices", "modules"):
        planes[key] = {k: [tuple(e) for e in v]
                       for k, v in planes.get(key, {}).items()}
    planes["host"] = [tuple(e) for e in planes["host"]]
    return planes
