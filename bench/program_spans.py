"""The program's own spans (``repro.core.spans``) in a benchmark run: the
per-layer metrics read from them, and the device's idle time attributed to
them on the profiler's clock.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> --spans <0|1> [--rehearsal]

runs one cell as ``bench/run.py`` does, with the program's span recorder on
when ``--spans 1``, and prints one JSON line: ``correct``, the cell's
end-to-end metrics, its host-clock per-layer metrics, the metrics of
``METRICS`` that read the program's spans, with ``--trace 1`` the device
metrics and ``idle_by_program_span``, ``device``, and with the recorder on
``resume_split`` and ``spans_by_name`` (count, seconds, median ms of each
span). With ``--spans 0 --trace 0`` it is the cell's ``bench/run.py --trace
0`` run; with ``--spans 1 --trace 0`` the same run with the recorder on,
which is what the recorder costs.

``bench/trace.py`` keeps only the harness's ``bench.`` spans, so this run
hands the cell's driver a reduction that also keeps the program's ``dse.``
spans, each with its host thread (its line of the trace), and adds
``idle_by_program_span`` to the reduced trace.

A reader of ``bench/metrics/`` that reads program spans returns None outside
its cell and where the program has no span recorder, and raises where the
recorder dropped records or the run lacks the span it reads.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, Iterable, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.trace import DEVICE_PLANE_PREFIX, gaps, union  # noqa: E402

#: prefix of the program's spans in the profiler's trace
PREFIX = "dse."
#: the training cells' trainer member, and its store's directory name
TRAINER = "trainer"

#: the metrics read from the program's spans, as ``BENCHMARK.json``'s
#: ``per_layer`` would list them; each is read by ``bench/metrics/<name>.py``
_CKPT = ["mamba2-370m.train.ckpt10"]
_SERVE = ["mamba2-370m.serve.agent4"]
METRICS = [
    dict(name="save_hold_s", unit="s", layer="protocol", moves="ckpt_tokens_per_s", workloads=_CKPT),
    dict(name="save_fetch_s", unit="s", layer="state objects", moves="ckpt_tokens_per_s", workloads=_CKPT),
    dict(name="save_encode_s", unit="s", layer="state objects", moves="ckpt_tokens_per_s", workloads=_CKPT),
    dict(name="save_durable_s", unit="s", layer="state objects", moves="ckpt_tokens_per_s", workloads=_CKPT),
    dict(name="save_bytes", unit="B", layer="state objects", moves="ckpt_tokens_per_s", workloads=_CKPT),
    dict(name="replacement_init_s", unit="s", layer="state objects", moves="resume_s", workloads=_CKPT),
    dict(name="restore_read_s", unit="s", layer="state objects", moves="resume_s", workloads=_CKPT),
    dict(name="restore_decode_s", unit="s", layer="state objects", moves="resume_s", workloads=_CKPT),
    dict(name="restore_upload_s", unit="s", layer="state objects", moves="resume_s", workloads=_CKPT),
    dict(name="barrier_p90_ms", unit="ms", layer="protocol", moves="turn_p90_ms", workloads=_SERVE),
    dict(name="release_write_p90_ms", unit="ms", layer="state objects", moves="turn_p90_ms", workloads=_SERVE),
    dict(name="action_wait_ms", unit="ms", layer="protocol", moves="turn_p90_ms", workloads=_SERVE),
    dict(name="dispatch_ms", unit="ms", layer="state objects", moves="turn_p90_ms", workloads=_SERVE),
]


# --------------------------------------------------------------------------- #
# the readers' side: records of one run                                       #
# --------------------------------------------------------------------------- #
def in_cell(run, cell: str) -> bool:
    """``ckpt``: a training run that saves (and resumes); ``serve``: a
    serving run."""
    if cell == "ckpt":
        return run.get("driver") == "train" and bool(run.get("save_stalls"))
    return run.get("driver") == cell


def records(run, cell: str):
    """The program's span records of this run, or None outside ``cell`` and
    where the program has no span recorder. Raises where it dropped any."""
    if not in_cell(run, cell):
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    if spans.dropped():
        raise RuntimeError(f"the span recorder dropped {spans.dropped()} records")
    return spans.records()


def named(recs: Iterable, name: str, **attrs) -> List:
    return [r for r in recs if r.name == name
            and all(r.attrs.get(k) == v for k, v in attrs.items())]


def need(found: List, what: str) -> List:
    if not found:
        raise RuntimeError(f"the run holds no {what} span: was the span recorder on?")
    return found


def below(recs: Sequence, root) -> List:
    """Every record nested under ``root`` (its children, theirs, ...)."""
    children: Dict[int, list] = {}
    for r in recs:
        children.setdefault(r.parent, []).append(r)
    out, todo = [], [root.id]
    while todo:
        for c in children.get(todo.pop(), ()):
            out.append(c)
            todo.append(c.id)
    return out


def trainer_saves(recs: Sequence) -> List:
    """The trainer's ``persist`` spans that saved a version after the first
    (version 0 is the connect-time save)."""
    saves = [r for r in named(recs, "persist", so_id=TRAINER) if r.attrs.get("version", 0) > 0]
    return need(saves, "trainer persist (version > 0)")


def trainer_restore(recs: Sequence):
    """The trainer's last ``restore`` and the records nested under it."""
    r = need(named(recs, "restore", so_id=TRAINER), "trainer restore")[-1]
    return r, below(recs, r)


def seconds(recs: Iterable) -> float:
    return sum(r.seconds for r in recs)


def median(values: Iterable[float]) -> float:
    return statistics.median(list(values))


# --------------------------------------------------------------------------- #
# the trace's side: program spans and the idle time under them               #
# --------------------------------------------------------------------------- #
def load_program_spans(out_dir: str) -> List[Tuple[str, float, float, Tuple[str, int]]]:
    """The ``dse.`` spans of the newest ``.xplane.pb`` under ``out_dir``:
    ``(name, start, end, thread)``, times in seconds on the trace's clock,
    ``thread`` the host plane and line that holds the span."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append((e.name[len(PREFIX):], s, s + e.duration_ns * 1e-9, (plane.name, i)))
    return out


def _segments(program_spans, lo: float, hi: float) -> List[Tuple[float, float, List[str]]]:
    """[lo, hi] cut where any program span starts or ends, each piece with
    the innermost open span of every host thread that has one open."""
    edges = []
    for k, (_, s, e, _) in enumerate(program_spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1, k), (e, 0, k)]
    edges.sort()  # at one instant, ends before starts
    open_: Dict[object, List[int]] = {}
    segs, t = [], lo

    def owners() -> List[str]:
        # a thread's spans nest: its innermost is the latest to start
        return [program_spans[max(ks, key=lambda k: (program_spans[k][1], -program_spans[k][2]))][0]
                for ks in open_.values()]

    for at, start, k in edges:
        if at > t:
            segs.append((t, at, owners()))
            t = at
        th = program_spans[k][3]
        if start:
            open_.setdefault(th, []).append(k)
        else:
            open_[th].remove(k)
            if not open_[th]:
                del open_[th]
    if hi > t:
        segs.append((t, hi, owners()))
    return segs


def idle_by_program_span(events: Dict[str, object], top: int = 10) -> Optional[List[list]]:
    """Seconds of the window's device idle time by program span, top
    ``top``: each idle instant is split evenly among the host threads that
    have a program span open then, each giving it to its innermost open
    span; an instant with none open counts as ``none``, which is always
    listed. Idle time is averaged over the devices that ran any operation,
    as ``busy_s`` is. None where the trace holds no window span or no
    device operation."""
    windows = [(s, e) for n, s, e in events["spans"] if n == "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    per_device = []
    for _, dev in sorted(events["devices"].items()):
        ops = [(s, e) for _, s, e in dev["ops"]] or [(s, e) for _, s, e in dev["modules"]]
        busy = union(ops, lo, hi)
        if busy:
            per_device.append(gaps(busy, lo, hi))
    if not per_device:
        return None
    segs = _segments(events["program_spans"], lo, hi)
    out: Dict[str, float] = {}
    w = 1.0 / len(per_device)
    for idle in per_device:
        j = 0
        for a, b in idle:
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < b:
                s, e, names = segs[k]
                ov = (min(b, e) - max(a, s)) * w
                if ov > 0:
                    for n in names or ["none"]:
                        out[n] = out.get(n, 0.0) + ov / max(1, len(names))
                k += 1
    ranked = sorted(out.items(), key=lambda kv: -kv[1])[:top]
    if "none" not in dict(ranked):
        ranked.append(("none", out.get("none", 0.0)))
    return [[n, t] for n, t in ranked]


@contextlib.contextmanager
def with_program_spans(driver):
    """Within the block, ``driver``'s trace reduction also keeps the
    program's spans and adds ``idle_by_program_span`` to what it returns."""
    load, reduce = driver.load_events, driver.reduce_trace

    def load_all(out_dir):
        events = load(out_dir)
        events["program_spans"] = load_program_spans(out_dir)
        return events

    def reduce_all(events, top=10):
        reduced = reduce(events, top)
        if reduced is not None:
            reduced["idle_by_program_span"] = idle_by_program_span(events, top)
        return reduced

    driver.load_events, driver.reduce_trace = load_all, reduce_all
    try:
        yield
    finally:
        driver.load_events, driver.reduce_trace = load, reduce


# --------------------------------------------------------------------------- #
# the run                                                                     #
# --------------------------------------------------------------------------- #
def resume_split(recs) -> Optional[Dict[str, float]]:
    """Seconds of the trainer's last resume by part: the replacement's
    ``trainer.init``, its ``connect`` and, inside that, the ``restore``'s
    read, decode and upload."""
    found = named(recs, "restore", so_id=TRAINER)
    if not found:
        return None
    restore, inside = trainer_restore(recs)
    connect = [r for r in named(recs, "connect", so_id=TRAINER) if r.id == restore.parent]
    inits = [r for r in named(recs, "trainer.init") if r.end_ns <= restore.start_ns]
    return {
        "init": inits[-1].seconds if inits else None,
        "connect": connect[0].seconds if connect else None,
        "restore": restore.seconds,
        "read": seconds(named(inside, "store.read", store=TRAINER)),
        "decode": seconds(named(inside, "trainer.decode")),
        "upload": seconds(named(inside, "trainer.upload")),
    }


def summary(recs) -> Dict[str, list]:
    """Per span name: [count, summed seconds, median ms]."""
    by: Dict[str, List[float]] = {}
    for r in recs:
        by.setdefault(r.name, []).append(r.seconds)
    return {n: [len(v), sum(v), median(v) * 1e3] for n, v in sorted(by.items())}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on any device at the rehearsal sizes (tests only)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    from bench.run import log, setup_jax
    from repro.core import spans

    cell = harness.cell(args.workload)
    conf = harness.config(cell["config"], args.rehearsal)
    traffic = harness.traffic(cell["traffic"], args.rehearsal)
    jax = setup_jax(args.rehearsal)
    dev = jax.devices()[0]
    if not args.rehearsal and dev.platform != "tpu":
        log(f"bench/program_spans.py: needs a TPU; JAX's first device is {dev.platform}")
        return 3
    spans.clear()
    if args.spans:
        spans.enable()
    log(f"device: {dev.platform} {dev.device_kind}; cell {cell['name']}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}, spans {args.spans}")
    ctx = SimpleNamespace(
        cell=cell, conf=conf, traffic=traffic, cfg=harness.program_config(conf),
        ref=harness.reference(conf), seed=args.seed, seed32=harness.seed32(args.seed),
        seconds=args.seconds, trace=bool(args.trace), rehearsal=args.rehearsal,
        run_dir=harness.RUN_DIR, t_start=T_START, log=log,
    )
    driver = harness.driver(traffic["driver"])
    shutil.rmtree(ctx.run_dir, ignore_errors=True)
    try:
        with with_program_spans(driver):
            res = driver.run(ctx)
    finally:
        shutil.rmtree(ctx.run_dir, ignore_errors=True)
        spans.disable()

    run = dict(res["readings"], trace=res["trace"], device_kind=dev.device_kind,
               rehearsal=args.rehearsal)
    metrics = {m["name"]: res["end_to_end"][m["name"]]
               for m in harness.cell_metrics(cell["name"], "end_to_end")}
    for m in harness.cell_metrics(cell["name"], "per_layer"):
        if m["source"] == "host_clock" or args.trace:
            value = harness.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = value
    if args.spans:
        for m in METRICS:
            value = harness.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = value
    out = {"correct": res["checks"].correct, "attempted": res["attempted"],
           "failed": res["failed"], "spans": args.spans, "trace": args.trace,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "memory_peak_bytes": res["memory_peak_bytes"]}}
    recs = spans.records()
    if recs:
        out["resume_split"] = resume_split(recs)
        out["spans_by_name"] = summary(recs)
    reduced = res["trace"]
    if reduced is not None:
        out["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["idle_gaps"] = reduced["idle_gaps"]
        out["idle_by_program_span"] = reduced.get("idle_by_program_span")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
