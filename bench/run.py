"""Chip benchmark of DSE-protected training and serving.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run: it finds the cell in ``BENCHMARK.json`` (its
configuration and traffic files under ``bench/``), loads and warms up,
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints one JSON line as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared, with its limit. The same numbers are the
last lines of standard error.

It exits non-zero, printing no result, unless JAX's first device is a TPU
that the peaks table knows and there are as many as the cell asks for.
``--rehearsal`` runs the same path on any device at the configuration's
rehearsal sizes, for the tests; its numbers are never a measurement.

Every save and trace goes under ``.bench_run/`` in the checkout, removed
before the process exits. JAX's compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/`` in the
checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on any device at the rehearsal sizes (tests only)")
    return ap.parse_args(argv)


def setup_jax(rehearsal: bool):
    import os

    import jax

    from bench.harness import CACHE_DIR

    if rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("bench/run.py: the system under test (src/repro) is not in this checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    cell = harness.cell(args.workload)
    conf = harness.config(cell["config"], args.rehearsal)
    traffic = harness.traffic(cell["traffic"], args.rehearsal)
    jax = setup_jax(args.rehearsal)
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearsal:
        if dev.platform != "tpu":
            log(f"bench/run.py: needs a TPU; JAX's first device is {dev.platform}")
            return 3
        if len(devices) < cell["chips"]:
            log(f"bench/run.py: {cell['name']} needs {cell['chips']} chips, JAX sees {len(devices)}")
            return 3
        from bench.peaks import peaks

        peaks(dev.device_kind)  # a device the table does not know is an error
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; cell {cell['name']}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}"
        + (" (rehearsal sizes)" if args.rehearsal else ""))

    ctx = SimpleNamespace(
        cell=cell, conf=conf, traffic=traffic, cfg=harness.program_config(conf),
        ref=harness.reference(conf), seed=args.seed, seed32=harness.seed32(args.seed),
        seconds=args.seconds, trace=bool(args.trace), rehearsal=args.rehearsal,
        run_dir=harness.RUN_DIR, t_start=T_START, log=log,
    )
    shutil.rmtree(ctx.run_dir, ignore_errors=True)
    try:
        res = harness.driver(traffic["driver"]).run(ctx)
    finally:
        shutil.rmtree(ctx.run_dir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics, breakdown = {}, None
    if args.trace:
        reduced = res["trace"]
        if reduced is not None:
            log(f"trace: window {reduced['window_s']!r} s, busy {reduced['busy_s']!r} s, "
                f"programs {reduced['modules']}")
        run = dict(res["readings"], trace=reduced, device_kind=dev.device_kind,
                   rehearsal=args.rehearsal)
        for m in harness.cell_metrics(cell["name"], "per_layer"):
            value = harness.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None and not args.rehearsal:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    else:
        for m in harness.cell_metrics(cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}

    checks = res["checks"]
    for name, c in checks.items.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} {'ok' if ok else 'FAILED'}"
            + (f" ({c['why']})" if "why" in c else ""))
    out = {"correct": checks.correct, "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
