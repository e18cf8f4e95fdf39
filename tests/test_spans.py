"""Program spans (``repro.core.spans``): off by default, nested per thread,
bounded, written into the profiler's trace as ``dse.<name>``, invisible to
the deterministic simulator; and the trainer's leaf-by-leaf snapshot that
the save spans time."""
from __future__ import annotations

import glob
import io
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import drain_io
from repro.core import spans


@pytest.fixture
def recording():
    """The process's recorder, on and empty, restored off and empty."""
    spans.clear()
    spans.enable()
    try:
        yield
    finally:
        spans.disable()
        spans.clear()


def by_name(records, name):
    return [r for r in records if r.name == name]


def test_off_records_nothing():
    spans.disable()
    spans.clear()
    with spans.span("persist", so_id="a") as sp:
        sp.set(version=3)
    assert spans.span("x") is spans.span("y", so_id="b")  # one shared no-op
    assert spans.records() == [] and spans.dropped() == 0


def test_spans_nest_per_thread(recording):
    inner_started = threading.Event()
    release = threading.Event()

    def other():
        with spans.span("other", so_id="t"):
            inner_started.set()
            release.wait(5)

    with spans.span("outer", so_id="m") as outer:
        t = threading.Thread(target=other)
        t.start()
        assert inner_started.wait(5)
        with spans.span("inner"):
            pass
        release.set()
        t.join(5)
        assert not t.is_alive()
        outer.set(version=7)
    recs = spans.records()
    (o,), (i,), (x,) = by_name(recs, "outer"), by_name(recs, "inner"), by_name(recs, "other")
    assert o.parent == 0 and i.parent == o.id
    # the other thread's span opened while ``outer`` was open, on another thread
    assert x.parent == 0 and x.thread != o.thread
    assert o.attrs == {"so_id": "m", "version": 7}
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    assert len({o.id, i.id, x.id}) == 3


def test_a_handed_off_span_is_the_child_of_the_span_it_left(recording):
    assert spans.current() == 0

    def behind(handed_off):
        with spans.span("behind", parent=handed_off):
            with spans.span("part"):
                pass

    with spans.span("outer"):
        here = spans.current()
        t = threading.Thread(target=behind, args=(here,))
        t.start()
        t.join(5)
    recs = spans.records()
    (o,), (b,), (part,) = by_name(recs, "outer"), by_name(recs, "behind"), by_name(recs, "part")
    assert o.id == here and b.parent == o.id and b.thread != o.thread
    assert part.parent == b.id and b.attrs == {}
    spans.disable()
    with spans.span("off"):
        assert spans.current() == 0


def test_a_full_buffer_counts_its_drops():
    rec = spans.Recorder(capacity=2)
    rec.on = True
    for n in range(5):
        with rec.span(f"s{n}"):
            pass
    assert [r.name for r in rec.records()] == ["s0", "s1"]
    assert rec.dropped == 3
    rec.clear()
    assert rec.records() == [] and rec.dropped == 0


def test_a_span_lands_in_the_profilers_trace(recording, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("persist", so_id="trainer", version=2):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [e for p in ProfileData.from_file(path).planes for line in p.lines
             for e in line.events if e.name == spans.PREFIX + "persist"]
    assert len(found) == 1
    assert dict(found[0].stats) == {"so_id": "trainer", "version": 2}


def test_a_sim_trace_is_the_same_with_the_recorder_on(tmp_path):
    from repro.sim.explore import run_one

    off = run_one("partition_merge", 7, tmp_path / "off")
    spans.clear()
    spans.enable()
    try:
        on = run_one("partition_merge", 7, tmp_path / "on")
        recorded = spans.records()
    finally:
        spans.disable()
        spans.clear()
    assert on.trace.encode() == off.trace.encode()
    assert on.events == off.events and on.virtual_time == off.virtual_time
    assert by_name(recorded, "persist") and by_name(recorded, "connect")


def _state():
    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
              "b": jnp.full((5,), -1.5, jnp.float16)}
    opt = {"m": jnp.ones((3, 4), jnp.float32), "count": jnp.asarray(3, jnp.int32)}
    return params, opt


def test_persist_restore_round_trip_through_the_archive(tmp_path):
    from repro.checkpoint import TrainerStateObject

    so = TrainerStateObject(tmp_path, _state, step_fn=None)
    done = threading.Event()
    so.Persist(1, b"meta", done.set)
    assert done.wait(10)
    want = [np.asarray(x) for x in jax.tree_util.tree_leaves((so.params, so.opt_state))]
    so.params = jax.tree_util.tree_map(jnp.zeros_like, so.params)
    assert so.Restore(1) == b"meta"
    got = jax.tree_util.tree_leaves((so.params, so.opt_state))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), b)
    # the body is an archive np.load reads, arrays in leaf order
    payload, _ = so.store.read(1)
    hdr, body = so._split_blob(payload)
    z = np.load(io.BytesIO(body))
    assert z.files == [f"arr_{i}" for i in range(len(want))]
    for k, b in zip(z.files, want):
        np.testing.assert_array_equal(z[k], b)


def test_a_trainer_save_has_a_fetch_and_an_encode_per_leaf(recording, tmp_path, monkeypatch):
    """Under the save's ``persist.state``: its one ``trainer.fetch``, after a
    ``trainer.save_wait`` where the previous save's write-behind still ran;
    handed off from it, on an IO thread, ``trainer.write_behind`` with the
    store's write and, handed off again to the thread that fills the
    archive, one ``trainer.encode`` per leaf and the ``trainer.join``."""
    from repro.checkpoint import TrainerStateObject, archive
    from repro.core import LocalCluster

    gate = threading.Event()
    fill = archive.Archive.fill

    def held(*args):
        assert gate.wait(60)
        return fill(*args)

    monkeypatch.setattr(archive.Archive, "fill", held)

    def step(p, o, batch):
        return jax.tree_util.tree_map(lambda x: x + 1, p), o, jnp.float32(0.5)

    def exclusive_waits():
        return len([r for r in by_name(spans.records(), "epoch.exclusive_wait")
                    if r.attrs["so_id"] == "trainer"])

    cluster = LocalCluster(tmp_path, refresh_interval=None, group_commit_interval=3600)
    gate.set()  # the connect-time save runs through
    try:
        trainer = cluster.add("trainer", lambda: TrainerStateObject(
            tmp_path / "trainer", _state, step))
        drain_io()
        gate.clear()
        labels = []
        assert trainer.train_on(0, np.zeros((1, 4), np.int32)) is not None
        labels.append(trainer.runtime.persist_if_dirty())
        assert trainer.train_on(1, np.zeros((1, 4), np.int32)) is not None
        waits = exclusive_waits()
        second = threading.Thread(
            target=lambda: labels.append(trainer.runtime.persist_if_dirty()))
        second.start()
        deadline = time.monotonic() + 60
        while exclusive_waits() == waits:  # until the second save holds the epoch
            assert time.monotonic() < deadline
            time.sleep(0.005)
        time.sleep(0.1)
        gate.set()
        second.join(60)
        assert not second.is_alive()
        drain_io()
    finally:
        gate.set()
        cluster.shutdown()
    assert labels == [1, 2]
    recs = spans.records()
    children = {}
    for r in recs:
        children.setdefault(r.parent, []).append(r)

    def below(r):
        for c in children.get(r.id, []):
            yield c
            yield from below(c)

    n = len(jax.tree_util.tree_leaves(_state()))
    for version, first in ((1, ["trainer.fetch"]), (2, ["trainer.save_wait", "trainer.fetch"])):
        (save,) = [r for r in by_name(recs, "persist")
                   if r.attrs == {"so_id": "trainer", "version": version}]
        assert [r.name for r in children[save.id]] == ["epoch.exclusive_wait", "persist.state"]
        state = children[save.id][1]
        assert [r.name for r in children[state.id]] == first + ["trainer.write_behind"]
        (behind,) = by_name(children[state.id], "trainer.write_behind")
        assert behind.thread != save.thread and behind.attrs == {"version": version}
        assert behind.start_ns >= state.start_ns
        inside = list(below(behind))
        assert sorted(r.attrs["leaf"] for r in by_name(inside, "trainer.encode")) == list(range(n))
        assert len(by_name(inside, "trainer.join")) == 1
        (write,) = by_name(inside, "store.write")
        assert write.attrs["version"] == version and write.thread == behind.thread
        fills = {r.thread for r in inside if r.name in ("trainer.encode", "trainer.join")}
        # the fill runs while the write waits on it; a thread's ident is
        # reused once it has ended, so only these two are told apart
        assert len(fills) == 1 and behind.thread not in fills
    (init,) = by_name(recs, "trainer.init")
    assert init.end_ns <= save.start_ns
