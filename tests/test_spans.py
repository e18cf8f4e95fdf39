"""Program spans (``repro.core.spans``): off by default, nested per thread,
bounded, written into the profiler's trace as ``dse.<name>``, invisible to
the deterministic simulator; and the trainer's leaf-by-leaf snapshot that
the save spans time."""
from __future__ import annotations

import glob
import io
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def test_a_trainer_save_has_a_fetch_and_an_encode_per_leaf(recording, tmp_path):
    from repro.checkpoint import TrainerStateObject
    from repro.core import LocalCluster

    def step(p, o, batch):
        return jax.tree_util.tree_map(lambda x: x + 1, p), o, jnp.float32(0.5)

    cluster = LocalCluster(tmp_path, refresh_interval=None, group_commit_interval=3600)
    try:
        trainer = cluster.add("trainer", lambda: TrainerStateObject(
            tmp_path / "trainer", _state, step))
        assert trainer.train_on(0, np.zeros((1, 4), np.int32)) is not None
        label = trainer.runtime.persist_if_dirty()
    finally:
        cluster.shutdown()
    assert label == 1
    recs = spans.records()
    (save,) = [r for r in by_name(recs, "persist")
               if r.attrs == {"so_id": "trainer", "version": 1}]
    children = {}
    for r in recs:
        children.setdefault(r.parent, []).append(r)

    def below(r):
        for c in children.get(r.id, []):
            yield c
            yield from below(c)

    inside = list(below(save))
    n = len(jax.tree_util.tree_leaves(_state()))
    for name in ("trainer.fetch", "trainer.encode"):
        assert sorted(r.attrs["leaf"] for r in by_name(inside, name)) == list(range(n))
    assert len(by_name(inside, "trainer.join")) == 1
    assert [r.name for r in children[save.id]] == ["epoch.exclusive_wait", "persist.state"]
    (init,) = by_name(recs, "trainer.init")
    assert init.end_ns <= save.start_ns
