"""From a jax.profiler trace to numbers: device busy and idle time, time
per operation, kernel and collective time, and the longest idle gaps named
by what the host was doing.

The arithmetic works on plain lists of (name, start_ns, duration_ns), so
it is tested on hand-made intervals and on the small recorded trace in
tests/; only `read_trace` knows the profiler's file format.

Planes (looked at by hand on the chip tool's v5e, jax 0.9.0): each chip is
a plane "/device:TPU:<n>" whose line "XLA Ops" holds one event per executed
HLO operation and whose line "XLA Modules" holds one event per jitted
program; the host is "/host:CPU", whose thread lines hold TraceAnnotation
spans. On the CPU backend (rehearsal only) there is no device plane and the
executor threads "tf_XLAPjRtCpuClient/*" of the host plane stand in for one.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast")
SPAN_PREFIX = "bench."


def is_collective(name: str) -> bool:
    return any(m in short_name(name) for m in COLLECTIVE_MARKS)


def is_mosaic(name: str) -> bool:
    """A Mosaic (Pallas TPU) kernel: on the v5e an "XLA Ops" event is
    named by its whole HLO instruction, and a kernel's says so."""
    return "tpu_custom_call" in name


def short_name(name: str) -> str:
    """`%fusion.12 = bf16[..] fusion(..)` -> `fusion.12`. A Mosaic kernel
    keeps its jax name without the instance number, under "mosaic:", so
    that the twelve layers' calls of one kernel are one row."""
    head = name.split(" = ", 1)[0].lstrip("%")
    if is_mosaic(name):
        return "mosaic:" + head.rsplit(".", 1)[0]
    return head


# ---------------------------------------------------------------------------
# taking a trace (inside the process that owns the chip)
# ---------------------------------------------------------------------------
def start(trace_dir: str):
    """Start the profiler with Python call tracing off: the benchmark's
    own TraceAnnotation spans are all it needs from the host, and the
    Python tracer slows the host it is measuring."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1     # TraceAnnotation spans, little else
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop(trace_dir: str, platform: str) -> dict:
    """Stop the profiler and return the raw events of the trace; the
    trace files are removed (they are large)."""
    import time

    import jax

    t0 = time.time()
    jax.profiler.stop_trace()
    t1 = time.time()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file, got {files}")
    try:
        trace = read_trace(files[0], platform)
        trace["cost"] = {"stop_trace_s": t1 - t0,
                         "read_trace_s": time.time() - t1,
                         "file_bytes": os.path.getsize(files[0])}
        return trace
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock; free when no trace runs."""
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


def read_trace(path: str, platform: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns), ...]},
        "modules": {plane: [...]}, "spans": [...]} from an .xplane.pb."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, modules, spans = {}, {}, []

    def events(line):
        return [(e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]

    for plane in data.planes:
        if platform == "tpu" and plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(plane.name, []).extend(events(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = events(line)
                spans.extend(e for e in evs if e[0].startswith(SPAN_PREFIX))
                if platform != "tpu" and \
                        line.name.startswith("tf_XLAPjRtCpuClient"):
                    devices.setdefault("/host:CPU-as-device", []).extend(
                        e for e in evs if e[2] > 0
                        and not e[0].startswith(("end:", "Threadpool")))
    return {"devices": devices, "modules": modules, "spans": spans}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """The parts of merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _split_by_span(s, e, spans):
    """[(span name or "no-span", ns), ...] covering the gap [s, e]: each
    span gets the part of the gap it overlaps, the rest is "no-span"
    (the benchmark's spans do not nest)."""
    parts, covered = [], 0.0
    for name, ss, dd in spans:
        ov = min(e, ss + dd) - max(s, ss)
        if ov > 0:
            parts.append((name, ov))
            covered += ov
    if e - s - covered > 0:
        parts.append(("no-span", e - s - covered))
    return parts


def idle_share_percent(reduced: dict):
    """1 - busy / window of a reduced trace, in percent; None where the
    trace holds no device operation."""
    if not reduced or not reduced.get("window_s"):
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce(trace: dict, top: int = 10) -> dict:
    """The reduced trace every per-layer reader works from. Times in
    seconds. Busy, idle, collective and kernel times are averaged over
    the device planes; gaps and the op table come from the first plane.

    window_s            first device op start to last device op end,
                        over all planes
    busy_s              union of device-op intervals, mean over planes
    mosaic_s            time in Mosaic kernels, mean over planes
    mosaic_by_name      {"mosaic:<jax name>": seconds}, first plane
    collective_s        collective-op time, mean over planes
    collective_exposed_s  the part of it during which no other op ran
    device_ops          [[name, seconds], ...] the `top` largest
    idle_gaps           [[label, seconds], ...] the `top` longest gaps,
                        summed by label; a label is the host span that
                        overlaps that part of the gap (or "no-span") and
                        the program or op that ended the gap
    """
    devs = {k: [e for e in v if e[2] > 0]
            for k, v in sorted(trace["devices"].items())}
    devs = {k: v for k, v in devs.items() if v}
    if not devs:
        return {}
    t0 = min(s for v in devs.values() for _, s, _ in v)
    t1 = max(s + d for v in devs.values() for _, s, d in v)
    n = len(devs)
    busy = coll = exposed = mosaic = 0.0
    for evs in devs.values():
        busy += total(merge((s, s + d) for _, s, d in evs))
        c = merge((s, s + d) for nm, s, d in evs if is_collective(nm))
        rest = merge((s, s + d) for nm, s, d in evs
                     if not is_collective(nm))
        coll += total(c)
        exposed += total(subtract(c, rest))
        mosaic += sum(d for nm, _, d in evs if is_mosaic(nm))
    first = next(iter(devs))
    op_s = {}
    for nm, _, d in devs[first]:
        nm = short_name(nm)
        op_s[nm] = op_s.get(nm, 0.0) + d
    # Idle gaps on the first plane, named by host span and by what came
    # next on the device.
    ops = sorted(devs[first], key=lambda e: e[1])
    mods = sorted(trace["modules"].get(first, []), key=lambda e: e[1])
    gaps, end, j = {}, None, 0
    for nm, s, d in ops:
        if end is not None and s > end:
            while j < len(mods) and mods[j][1] + mods[j][2] <= s:
                j += 1
            nxt = mods[j][0] if j < len(mods) and mods[j][1] <= s + 1 \
                else short_name(nm)
            for span_name, part in _split_by_span(end, s, trace["spans"]):
                label = f"{span_name}->{nxt.split('(')[0]}"
                gaps[label] = gaps.get(label, 0.0) + part
        end = s + d if end is None else max(end, s + d)
    ns = 1e-9
    rank = lambda d: [[k, v * ns] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "planes": list(devs), "window_s": (t1 - t0) * ns,
        "busy_s": busy / n * ns,
        "mosaic_s": mosaic / n * ns,
        "mosaic_by_name": {k: v * ns for k, v in op_s.items()
                           if k.startswith("mosaic:")},
        "collective_s": coll / n * ns,
        "collective_exposed_s": exposed / n * ns,
        "device_ops": rank(op_s), "idle_gaps": rank(gaps),
        "n_device_events": sum(len(v) for v in devs.values()),
        "cost": trace.get("cost"),
    }
