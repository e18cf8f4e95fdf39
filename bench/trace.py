"""Profiler capture and the reduction from a device trace to metrics.

A traced run wraps a short sub-window of its work in ``capture``, with the
harness's own ``jax.profiler.TraceAnnotation`` spans around each call into a
layer (``SPAN_PREFIX`` + the call's name) and one span, ``WINDOW``, around
the whole sub-window. ``reduce_trace`` then reads, on the trace's own clock:

- busy: the union of the intervals in which an operation ran on a device,
  clipped to the window, averaged over the devices that ran any;
- idle gaps: the complement of busy in the window, each named by the host
  span that overlaps it most (``host idle`` where none does);
- top device operations by their summed time;
- per-program (XLA module) execution counts and summed device time, over
  every execution that overlaps the window, timed whole. The device's clock
  is mapped onto the host's only approximately, and the window's span opens
  and closes within milliseconds of its first and last program, so an
  execution is not dropped for seeming to reach past its edge; the capture
  holds no other execution of the traced work's programs, since each driver
  waits for its work before the capture opens.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW = SPAN_PREFIX + "window"
#: trace lines of a device plane that hold operations and programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:"

Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(out_dir: str):
    """Trace everything inside the block into ``out_dir`` (emptied first)."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span of the harness, written into the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def load_events(out_dir: str) -> Dict[str, object]:
    """Read the newest ``.xplane.pb`` under ``out_dir`` into plain lists:
    ``{"devices": {plane: {"ops": [(name, start, end)], "modules": [...]}},
    "spans": [(name, start, end)]}``, times in seconds."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    # an op's event name is its HLO text; keep the name
                    # ("%fusion.12" of "%fusion.12 = f32[...] fusion(...)")
                    n = e.name.split(" = ", 1)[0] if key == "ops" else e.name
                    dev[key].append((n, s, s + e.duration_ns * 1e-9))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((e.name[len(SPAN_PREFIX):], s, s + e.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted, disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    best, name = 0.0, "host idle"
    for n, s, e in spans:
        if n == "window":
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def reduce_trace(events: Dict[str, object], top: int = 10) -> Optional[Dict[str, object]]:
    """Busy and idle seconds, the longest idle gaps by host span, the top
    device operations and per-program times, over the ``window`` span.
    None where the trace holds no window span or no device operation."""
    spans = events["spans"]
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    busy_s, all_gaps = [], []
    op_time: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for plane, dev in sorted(events["devices"].items()):
        ops = [(s, e) for _, s, e in dev["ops"]] or [(s, e) for _, s, e in dev["modules"]]
        busy = union(ops, lo, hi)
        if not busy:
            continue
        busy_s.append(sum(e - s for s, e in busy))
        all_gaps += gaps(busy, lo, hi)
        for n, s, e in dev["ops"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[n] = op_time.get(n, 0.0) + d
        for n, s, e in dev["modules"]:
            if e > lo and s < hi:
                m = modules.setdefault(n, [0, 0.0, 0, s - lo, hi - e])
                m[0] += 1
                m[1] += e - s
                m[2] += not (lo <= s and e <= hi)
                m[3], m[4] = min(m[3], s - lo), min(m[4], hi - e)
    if not busy_s:
        return None
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_s) / len(busy_s),
        "devices": len(busy_s),
        "idle_gaps": [[_name_gap(g, spans), g[1] - g[0]] for g in all_gaps[:top]],
        "device_ops": [[n, t] for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        # "edge": executions that reach past the window's span; "lead_s",
        # "tail_s": from the span's start to the first execution's, and from
        # the last execution's end to the span's (below 0 past the span)
        "modules": {n: {"count": c, "seconds": t, "edge": x, "lead_s": a, "tail_s": b}
                    for n, (c, t, x, a, b) in modules.items()},
    }


def module_time(reduced: Dict[str, object], module: str) -> Tuple[int, float]:
    """(executions, summed device seconds) of the programs named ``module``
    (the trace appends a program id: ``jit_train_step(12)``)."""
    n, t = 0, 0.0
    for name, m in reduced["modules"].items():
        if name.split("(")[0] == module:
            n += m["count"]
            t += m["seconds"]
    return n, t


def idle_percent(run) -> Optional[float]:
    """Device idle share (%) of a run's traced sub-window: 1 minus busy over
    the window. None for a run with no device trace (or a rehearsal)."""
    t = run.get("trace")
    if t is None or run.get("rehearsal"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
