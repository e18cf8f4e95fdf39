"""What the benchmark finds by name, and the pieces every driver shares.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix:

- ``bench/configs/<config>.json``: the sizes as they are run, the program's
  configuration module that must hold the same sizes (``program``), the
  plain reference beside it (``bench/reference/<reference>.py``), and the
  limits of the numbers that decide ``correct``;
- ``bench/traffic/<traffic>.json``: the parameters of one mix, and the
  driver that runs it (``bench/drivers/<driver>.py``);
- each per-layer metric is read by ``bench/metrics/<name>.py``, whose
  ``read(run)`` returns a number, or None where the run holds nothing to
  read.

A new cell, mix or metric is a new file (and, for a cell or a metric, a new
entry in ``BENCHMARK.json``); no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import statistics
import sys
import threading
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: every save and trace of a run goes here; emptied before and after a run
RUN_DIR = ROOT / ".bench_run"
#: JAX's persistent compilation cache where the environment names none
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def spec(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> Dict:
    for w in spec(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _with_rehearsal(d: Dict, rehearsal: bool) -> Dict:
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    if rehearsal:
        out.update(d.get("rehearsal", {}))
    return out


def config(name: str, rehearsal: bool = False) -> Dict:
    return _with_rehearsal(load_json(BENCH / "configs" / f"{name}.json"), rehearsal)


def traffic(name: str, rehearsal: bool = False) -> Dict:
    return _with_rehearsal(load_json(BENCH / "traffic" / f"{name}.json"), rehearsal)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts).replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sys.modules[name] = mod
    sp.loader.exec_module(mod)
    return mod


def driver(name: str) -> ModuleType:
    return load_module(BENCH / "drivers" / f"{name}.py")


def reference(conf: Dict) -> ModuleType:
    return load_module(BENCH / "reference" / f"{conf['reference']}.py")


def reader(metric: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{metric}.py")


def cell_metrics(cell_name: str, kind: str, root: Path = ROOT) -> List[Dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those that list
    it (an end-to-end metric with no list, ``setup_s``: every cell)."""
    s = spec(root)
    if kind == "end_to_end":
        return [m for m in s["end_to_end"] if cell_name in m.get("workloads", [cell_name])]
    return [m for m in s["per_layer"] if cell_name in m["workloads"]]


def program_config(conf: Dict):
    """The program's model configuration, checked against the file: a size
    that differs is an error, so the file always says what runs."""
    from repro.configs import get_config

    cfg = get_config(conf["program"], smoke=bool(conf.get("program_smoke")))
    s = cfg.ssm
    have = {
        "d_model": cfg.d_model, "n_layer": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "vocab_padded": cfg.vocab_padded, "d_state": s.d_state, "d_conv": s.d_conv,
        "expand": s.expand, "headdim": s.head_dim, "ngroups": s.n_groups,
        "chunk_size": s.chunk_size, "tie_embeddings": cfg.tie_embeddings,
        "norm_eps": cfg.norm_eps,
    }
    wrong = {k: (conf.get(k), v) for k, v in have.items() if conf.get(k) != v}
    if wrong:
        raise ValueError(f"configuration {conf['name']} (file, program) differ: {wrong}")
    return cfg


def seed32(seed: int) -> int:
    """A non-negative int32 drawn from any whole-number seed: the seed of
    the weights (JAX keys take 32 bits)."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0] & 0x7FFFFFFF)


def p_quantile(values: Sequence[float], q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least q of all
    values at or below it."""
    v = sorted(values)
    return v[max(0, int(np.ceil(q * len(v))) - 1)]


def drain_io(timeout: float = 300.0) -> None:
    """Wait for the program's background Persist writes to end, so a run's
    files are whole before its directory goes."""
    for t in threading.enumerate():
        if t.name.endswith("persist-io"):
            t.join(timeout=timeout)


def memory_peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks or [0]))


def leaf_norms_host(tree) -> Dict[str, float]:
    """Per-leaf float64 norms of a host (numpy) tree, keyed by path."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for p, x in flat}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Sequence[str]] = None) -> Tuple[float, str]:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of the reference's norm of that leaf and the
    median leaf's (the gap of the norms, not the norm of the difference),
    and that leaf."""
    keys = list(keep if keep is not None else ref)
    if set(keys) - set(prog):
        raise KeyError(f"leaves missing from the program: {sorted(set(keys) - set(prog))}")
    med = statistics.median(ref[k] for k in keys)
    return max((abs(prog[k] - ref[k]) / max(ref[k], med), k) for k in keys)


class Checks:
    """Numbers compared with their limits; ``correct`` is that none is over."""

    def __init__(self, limits: Dict[str, float]) -> None:
        self.limits = limits
        self.items: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(self.limits[name])}

    def fail(self, name: str, why: str) -> None:
        """A number that could not be read: it fails."""
        self.items[name] = {"value": None, "limit": float(self.limits[name]), "why": why}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            c["value"] is not None and c["value"] <= c["limit"] for c in self.items.values())

