"""Reduction of an ``--xprof`` capture (``.xplane.pb``) to what an
operator reads: device seconds by phase and unit (the trace's
operations joined with ``programs.scopes``), the kernels by name, and
every idle gap of the device over a millisecond laid against the
program's own ``veles.*`` spans (``tracing.annotated``), which sit on
the host plane of the same file and share its clock.

Reads the file with nothing but JAX (``jax.profiler.ProfileData``).
:func:`reduce_planes` is pure (plain lists in, a dict out), so the
test drives it on a small recorded plane set kept as JSON.
"""

import bisect
import glob
import os

from . import programs

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "veles."
#: Idle gaps shorter than this are not listed (seconds).
GAP_FLOOR_S = 1e-3
TOP = 12


def profile_options():
    """Host annotations on, the Python call tracer off (it writes an
    event for every Python call: hundreds of MB over a window)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def find_xplane(directory):
    paths = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError("no .xplane.pb under %s" % directory)
    return max(paths, key=os.path.getmtime)


def read_planes(path):
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "modules": {plane: [...]}, "host": [...]}``: the operations line
    and the modules line (one event an execution of a compiled
    program) of every device plane, and the ``veles.*`` spans of the
    host plane."""
    from jax.profiler import ProfileData
    devices, modules, host = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = devices if line.name == OPS_LINE else modules
                    into[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "modules": modules, "host": host}


def union(intervals):
    """Merged [start, end] intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """``[(name, start_ns, self_ns)]``: events of one line nest (a
    ``while`` encloses its body); a child's time is taken off its
    parent's."""
    out, stack = [], []     # stack of [name, start, end, self_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, _end, own = stack.pop()
            out.append((name, start, own))
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][3] -= dur
        stack.append([name, start, start + dur, dur])
    close(float("inf"))
    return out


def instruction_name(event_name):
    """``%fusion.1300 = f32[...] fusion(...)`` -> ``fusion.1300``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def span_name(event_name):
    """``veles.step.wait#k=v#`` -> ``step.wait``."""
    return event_name.split("#", 1)[0][len(SPAN_PREFIX):]


def covering(host, start, end):
    """``{span name: seconds}`` of a device gap that lie inside each
    ``veles.*`` span: where the host was while the device idled."""
    inside = {}
    for name, s, d in host:
        cover = min(end, s + d) - max(start, s)
        if cover > 0:
            name = span_name(name)
            inside[name] = inside.get(name, 0.0) + cover / 1e9
    return inside


def reduce_planes(planes, scopes=programs.scopes):
    """The reduced capture; None where it holds no device plane."""
    devices = planes["devices"]
    if not devices:
        return None
    first = min(e[1] for evs in devices.values() for e in evs)
    last = max(e[1] + e[2] for evs in devices.values() for e in evs)
    busy_ns, placed, kernels, gaps, by_program = 0.0, {}, {}, [], {}
    tables = {}     # program -> its scope table, asked for once
    for plane, events in devices.items():
        merged = union((s, s + d) for _n, s, d in events)
        busy_ns += sum(e - s for s, e in merged)
        gaps.extend((s1 - e0, e0, s1) for (_s0, e0), (s1, _e1)
                    in zip(merged, merged[1:])
                    if s1 - e0 >= GAP_FLOOR_S * 1e9)
        runs = sorted((s, s + d, n.split("(")[0])
                      for n, s, d in planes["modules"].get(plane, ()))
        starts = [r[0] for r in runs]
        for name, start, own in self_times(events):
            i = bisect.bisect_right(starts, start) - 1
            program = runs[i][2] if i >= 0 and start < runs[i][1] \
                else None
            by_program[program] = by_program.get(program, 0.0) + own
            if program not in tables:
                tables[program] = scopes(program[len("jit_"):]) \
                    if program and program.startswith("jit_") else None
            phase, unit, _inner = (tables[program] or {}).get(
                instruction_name(name), (None, None, None))
            key = (phase or "unscoped", unit or "-")
            placed[key] = placed.get(key, 0.0) + own
            if 'custom_call_target="tpu_custom_call"' in name:
                kernel = instruction_name(name).rsplit(".", 1)[0]
                entry = kernels.setdefault(kernel, [0, 0.0])
                entry[0] += 1
                entry[1] += own
    n = len(devices)
    return {
        "window_s": (last - first) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "devices": n,
        "programs": {k: v / 1e9 / n for k, v in by_program.items()},
        "placed": {k: v / 1e9 / n for k, v in placed.items()},
        "kernels": {k: [c / n, v / 1e9 / n]
                    for k, (c, v) in kernels.items()},
        "gaps": [[length / 1e9, (start - first) / 1e9,
                  covering(planes["host"], start, end)]
                 for length, start, end in sorted(gaps, reverse=True)],
        "spans": sorted({span_name(e[0]) for e in planes["host"]}),
    }


def reduce_dir(directory):
    """Reads and reduces the newest capture under ``directory``."""
    return reduce_planes(read_planes(find_xplane(directory)))


def report(reduced):
    """What the ``--xprof`` window prints when it closes."""
    if reduced is None:
        return "xprof: the capture holds no device plane"
    busy = reduced["busy_s"] or float("nan")
    lines = ["xprof: %d device(s), window %.4f s, busy %.4f s "
             "(idle %.2f%%); host spans: %s" % (
                 reduced["devices"], reduced["window_s"],
                 reduced["busy_s"],
                 100.0 * (1.0 - busy / reduced["window_s"]),
                 ", ".join(reduced["spans"]) or "none")]
    lines.append("device seconds by phase x unit (share of busy):")
    phases = {}
    for (phase, unit), seconds in reduced["placed"].items():
        phases.setdefault(phase, []).append((seconds, unit))
    for phase in list(programs.PHASES) + ["unscoped"]:
        units = sorted(phases.get(phase, ()), reverse=True)
        if not units:
            continue
        total = sum(s for s, _u in units)
        lines.append("  %-9s %8.4f s %5.1f%%  %s" % (
            phase, total, 100.0 * total / busy,
            "  ".join("%s %.4f" % (u, s) for s, u in units[:TOP])))
    lines.append("kernels (tpu_custom_call) by name:")
    for kernel, (calls, seconds) in sorted(
            reduced["kernels"].items(), key=lambda kv: -kv[1][1])[:TOP]:
        lines.append("  %-24s %6.0f calls %8.4f s  %7.3f ms a call" % (
            kernel, calls, seconds, 1e3 * seconds / max(calls, 1)))
    lines.append("idle gaps over %.0f ms (seconds, at, inside):" %
                 (GAP_FLOOR_S * 1e3))
    for length, at, inside in reduced["gaps"][:TOP]:
        lines.append("  %.6f s at %.4f s  %s" % (
            length, at, ", ".join(
                "%s %.6f" % (name, seconds) for name, seconds in
                sorted(inside.items(), key=lambda kv: -kv[1]))
            or "outside every veles.* span"))
    return "\n".join(lines)
