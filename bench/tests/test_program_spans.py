"""Tests of the metrics read from the program's own spans and of the idle
time attributed to them (``bench/program_spans.py``), on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_program_spans.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, program_spans  # noqa: E402
from repro.core import spans  # noqa: E402
from repro.core.spans import Record  # noqa: E402

CKPT = {"driver": "train", "save_stalls": [1.0]}
SERVE = {"driver": "serve"}
STEADY = {"driver": "train", "save_stalls": []}
NAMES = [m["name"] for m in program_spans.METRICS]
S = 10**9  # ns per second
MS = 10**6


@pytest.fixture(autouse=True)
def empty_recorder():
    spans.disable()
    spans.clear()
    yield
    spans.disable()
    spans.clear()


class Recs:
    """Synthetic records: ``add(name, seconds, parent=None, **attrs)``. A
    record starts where the last one under the same parent ended."""

    def __init__(self) -> None:
        self.out = []
        self.cursor = {0: 0}

    def add(self, name, dur, parent=None, thread=1, **attrs):
        up = 0 if parent is None else parent.id
        start = self.cursor.get(up, parent.start_ns if parent else 0) + 1
        r = Record(name, start, start + int(dur), thread, len(self.out) + 1, up, attrs)
        self.cursor[up] = r.end_ns
        self.cursor[r.id] = start
        self.out.append(r)
        return r


def ckpt_records():
    r = Recs()
    r.add("trainer.init", 1 * S)
    r.add("persist", 2 * S, so_id="trainer", version=0)
    r.add("store.write", 9 * S, store="trainer", version=0, bytes=10, thread=2)
    # three saves: hold 4, 6, 5 s; fetch 1, 2, 1.5 s; encode + join 2, 3, 2.5 s
    for v, hold, fetch, enc, join in [(1, 4, 1, 1.5, 0.5), (2, 6, 2, 2, 1), (3, 5, 1.5, 2, 0.5)]:
        p = r.add("persist", hold * S, so_id="trainer", version=v)
        r.add("epoch.exclusive_wait", 0.001 * S, p, so_id="trainer")
        st = r.add("persist.state", (hold - 0.01) * S, p, so_id="trainer", version=v)
        r.add("trainer.fetch", fetch * S, st, leaf=0)
        r.add("trainer.encode", enc * S, st, leaf=0)
        r.add("trainer.join", join * S, st)
        r.add("persist", 0.2 * S, so_id="metrics", version=v)
    for v, dur, n in [(1, 3, 100), (2, 4, 300), (3, 5, 200)]:
        r.add("store.write", dur * S, store="trainer", version=v, bytes=n, thread=2)
    # the kill: the replacement's init, then its connect and restore
    r.add("trainer.init", 2.5 * S)
    c = r.add("connect", 5 * S, so_id="trainer", version=3)
    rs = r.add("restore", 4 * S, c, so_id="trainer", version=3)
    r.add("store.read", 0.7 * S, rs, store="trainer", version=3, bytes=200)
    r.add("trainer.decode", 1.25 * S, rs)
    r.add("trainer.upload", 0.5 * S, rs)
    d = r.add("restore", 1 * S, so_id="data", version=3, thread=3)
    r.add("store.read", 0.9 * S, d, store="data", version=3, bytes=5, thread=3)
    return r.out


def serve_records():
    # ten barriers of 1..10 ms, ten writes of 2..20 ms, four tokens
    r = Recs()
    for i in range(1, 11):
        r.add("barrier", i * MS, so_id=f"s{i % 4}")
        r.add("store.write", 2 * i * MS, store=f"s{i % 4}", version=i, bytes=8)
    for i in range(1, 5):
        r.add("session.dispatch", i * MS, so_id="s0")
        r.add("session.sync", 3 * MS, so_id="s0")
    r.add("epoch.shared_wait", 4 * MS, so_id="s0")
    r.add("epoch.shared_wait", 2 * MS, so_id="s1")
    return r.out


CKPT_WANT = {
    "save_hold_s": 5.0, "save_fetch_s": 1.5, "save_encode_s": 2.5, "save_durable_s": 4.0,
    "save_bytes": 200, "replacement_init_s": 2.5, "restore_read_s": 0.7,
    "restore_decode_s": 1.25, "restore_upload_s": 0.5,
}
SERVE_WANT = {"barrier_p90_ms": 9.0, "release_write_p90_ms": 18.0, "action_wait_ms": 1.5,
              "dispatch_ms": 2.5}


def cell_of(name):
    """The run and the records of the cell a span metric is read in."""
    return (CKPT, ckpt_records()) if name in CKPT_WANT else (SERVE, serve_records())


def use(monkeypatch, recs, dropped=0):
    monkeypatch.setattr(spans, "records", lambda: list(recs))
    monkeypatch.setattr(spans, "dropped", lambda: dropped)


def test_every_span_metric_is_tabled_and_has_a_reader():
    assert sorted(NAMES) == sorted([*CKPT_WANT, *SERVE_WANT])
    listed = {m["name"] for m in harness.spec()["per_layer"]}
    cells = {w["name"] for w in harness.spec()["workloads"]}
    for m in program_spans.METRICS:
        assert m["name"] not in listed
        assert set(m["workloads"]) <= cells
        assert callable(harness.reader(m["name"]).read)


@pytest.mark.parametrize("name", NAMES)
def test_a_span_reader_reads_its_cell(name, monkeypatch):
    run, recs = cell_of(name)
    use(monkeypatch, recs)
    assert harness.reader(name).read(run) == pytest.approx({**CKPT_WANT, **SERVE_WANT}[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_span_reader_reads_nothing_in_other_cells(name, monkeypatch):
    use(monkeypatch, ckpt_records() + serve_records())
    others = [SERVE, STEADY] if name in CKPT_WANT else [CKPT, STEADY]
    for run in others:
        assert harness.reader(name).read(run) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_span_reader_raises_on_drops_or_a_missing_span(name, monkeypatch):
    run, recs = cell_of(name)
    use(monkeypatch, recs, dropped=3)
    with pytest.raises(RuntimeError, match="dropped 3"):
        harness.reader(name).read(run)
    # the other members' spans alone
    use(monkeypatch, [r for r in recs if r.attrs.get("so_id") == "metrics"])
    with pytest.raises(RuntimeError, match="holds no"):
        harness.reader(name).read(run)


def test_span_readers_read_nothing_without_the_programs_recorder(monkeypatch):
    """A program that has no ``repro.core.spans`` (the parent of the change
    that adds it) gives None, not an error."""
    import repro.core

    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    for name in NAMES:
        assert harness.reader(name).read(CKPT if name in CKPT_WANT else SERVE) is None


def test_idle_time_by_program_span_on_two_host_threads():
    a, b = ("/host:CPU", 0), ("/host:CPU", 1)
    events = {
        "devices": {"/device:TPU:0": {"ops": [("%f.1", 1.0, 2.0), ("%f.2", 4.0, 5.0)],
                                      "modules": []}},
        "spans": [("window", 0.0, 10.0), ("train_on", 0.0, 1.0)],
        "program_spans": [("persist", 0.5, 6.0, a), ("persist.state", 3.0, 6.0, a),
                          ("barrier", 2.5, 3.5, b), ("poll", 11.0, 12.0, b)],
    }
    got = dict(program_spans.idle_by_program_span(events))
    # idle [0, 1], [2, 4], [5, 10]: 8 s; [2.5, 3.5] is split between threads
    assert got == pytest.approx({"none": 4.5, "persist.state": 1.75, "persist": 1.25,
                                 "barrier": 0.5})
    assert list(got)[0] == "none"
    events["program_spans"] = []
    assert program_spans.idle_by_program_span(events) == [["none", pytest.approx(8.0)]]
    assert program_spans.idle_by_program_span({**events, "spans": []}) is None


def test_a_trace0_rehearsal_leaves_the_recorder_empty(capsys, tmp_path, monkeypatch):
    from bench import run as bench_run

    monkeypatch.setattr(harness, "RUN_DIR", tmp_path / "run")
    rc = bench_run.main(["--workload", "mamba2-370m.train.ckpt10", "--seed", "98765432109",
                         "--seconds", "1", "--trace", "0", "--rehearsal"])
    assert rc == 0
    assert not spans.enabled()
    assert spans.records() == [] and spans.dropped() == 0


@pytest.mark.parametrize("cell", [w["name"] for w in harness.spec()["workloads"]])
def test_the_spans_run_reads_each_span_metric_in_its_cell(cell, capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "RUN_DIR", tmp_path / "run")
    rc = program_spans.main(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "1",
                             "--trace", "1", "--spans", "1", "--rehearsal"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    want = {m["name"] for m in program_spans.METRICS if cell in m["workloads"]}
    got = set(out["metrics"]) & set(NAMES)
    assert got == want
    assert not spans.enabled()
    if ".ckpt" in cell:
        split = out["resume_split"]
        assert split["read"] + split["decode"] + split["upload"] <= split["restore"]
        assert split["restore"] <= split["connect"]
