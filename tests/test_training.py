"""DSE-resilient training loop tests: the paper's core claim transplanted
to training — speculative execution past checkpoints with rollback recovery
is EQUIVALENT to failure-free execution (bit-identical parameters), while
external observers never see rolled-back state."""
from __future__ import annotations

import io
import threading
import time
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import drain_io
from repro.configs import get_config
from repro.train import run_resilient_training

CFG = get_config("gemma_2b", smoke=True)
STEPS = 8


def test_loop_runs_and_losses_finite(tmp_path):
    res = run_resilient_training(tmp_path / "a", CFG, steps=4)
    assert res.final_step == 4
    assert len(res.metrics) == 4
    assert all(np.isfinite(l) for _, l in res.metrics)


def test_failure_run_equals_failure_free_run(tmp_path):
    base = run_resilient_training(tmp_path / "base", CFG, steps=STEPS)
    injected = run_resilient_training(
        tmp_path / "inj", CFG, steps=STEPS, kill_trainer_at=4
    )
    assert injected.rollbacks >= 1
    # THE durable-execution equivalence: identical final parameters
    assert injected.params_digest == base.params_digest
    assert injected.final_step == base.final_step == STEPS


def test_external_metrics_see_each_step_exactly_once(tmp_path):
    res = run_resilient_training(
        tmp_path / "m", CFG, steps=STEPS, kill_trainer_at=5
    )
    ext_steps = [s for s, _ in res.external_metrics]
    # failure transparency: no gaps, no duplicates, despite the rollback
    assert sorted(ext_steps) == list(range(STEPS))
    # and the speculative re-execution produced identical losses
    by_step = {}
    for s, l in res.metrics:
        by_step.setdefault(s, set()).add(round(l, 5))
    assert all(len(v) == 1 for v in by_step.values())


def test_data_pipeline_failure_recovers(tmp_path):
    base = run_resilient_training(tmp_path / "b2", CFG, steps=STEPS)
    injected = run_resilient_training(
        tmp_path / "d", CFG, steps=STEPS, kill_data_at=3
    )
    assert injected.params_digest == base.params_digest


def test_run_without_group_commit_saves_at_connect_and_end(tmp_path):
    """A group commit that never falls due leaves durability to the end of
    the run: every member is saved there, each at most once, and the export
    sees every step."""
    res = run_resilient_training(tmp_path / "g", CFG, steps=3, group_commit_interval=3600.0)
    assert [step for step, _, _ in res.saves] == [0, 3]
    assert sorted(s for s, _ in res.external_metrics) == [0, 1, 2]


def test_delta_codec_preserves_state(tmp_path):
    base = run_resilient_training(tmp_path / "b3", CFG, steps=STEPS)
    delta = run_resilient_training(
        tmp_path / "dc", CFG, steps=STEPS, kill_trainer_at=4, use_delta_codec=True
    )
    # int8 delta checkpoints restore to the same prefix the full snapshots
    # would; replayed steps give identical digests because restore happens
    # from a BASE version here (base_every=4) — and run must complete.
    assert delta.final_step == STEPS
    assert len(delta.external_metrics) == STEPS


def _persisted(so, version: int = 1) -> bytes:
    """``so.Persist(version)`` run to durability; the blob the store holds."""
    done = threading.Event()
    so.Persist(version, b"meta", done.set)
    assert done.wait(60)
    return so.store.read(version)[0]


def test_snapshot_blob_opens_as_zip64_archive(tmp_path):
    """A full-width snapshot is over 4 GB, so its archive carries zip64
    records, whose offsets are absolute: the archive must start the body
    Restore loads. More than 65535 leaves force those records at a tiny size."""
    from repro.checkpoint import TrainerStateObject

    leaves = [np.full(2, i, np.float32) for i in range(66_000)]
    so = TrainerStateObject(tmp_path, lambda: (leaves, []), step_fn=None)
    hdr, body = so._split_blob(_persisted(so))
    assert hdr["step"] == 0 and hdr["base"]
    archive = np.load(io.BytesIO(body))
    assert len(archive.files) == 66_000
    assert float(archive[archive.files[-1]][0]) == 65_999.0


def _savez_blob(prefix: bytes, leaves) -> bytes:
    """The blob as the trainer wrote it when it encoded under the epoch:
    header, then the archive ``np.savez`` writes, leaf by leaf."""
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_STORED, allowZip64=True) as z:
        for i, leaf in enumerate(leaves):
            with z.open(f"arr_{i}.npy", "w", force_zip64=True) as f:
                np.lib.format.write_array(f, leaf, allow_pickle=False)
    return prefix + buf.getvalue()


TREES = {
    "mixed": lambda: [np.arange(12, dtype=np.float32).reshape(3, 4),
                      np.full(5, -1.5, np.float16), np.asarray(3, np.int32),
                      np.zeros((0, 3), np.float32)],
    # one leaf over the encoder's copy chunk (8 MiB)
    "chunked": lambda: [np.arange(5 << 20, dtype=np.float32).reshape(-1, 1024),
                        np.ones(7, np.int8)],
}


@pytest.mark.parametrize("zip64_limit", ["zipfile", "tiny"])
@pytest.mark.parametrize("tree", sorted(TREES))
def test_write_behind_blob_is_the_savez_blob(tree, zip64_limit, tmp_path, monkeypatch):
    """The write-behind hands the store, and the store writes to disk, the
    bytes the savez-layout encoder made. A tiny zip64 limit moves sizes and
    offsets into the zip64 fields, as a snapshot over 2 GiB does."""
    from repro.checkpoint import TrainerStateObject
    from repro.core.state_object import VersionStore

    if zip64_limit == "tiny":
        monkeypatch.setattr(zipfile, "ZIP64_LIMIT", 64)
    leaves = TREES[tree]()
    so = TrainerStateObject(tmp_path, lambda: (leaves, []), step_fn=None)
    want = _savez_blob(so._header(None, True), leaves)
    assert bytes(_persisted(so)) == want
    assert VersionStore(tmp_path).read(1)[0] == want  # a fresh store reads the disk


def _small_state():
    return ({"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
            {"m": jnp.ones((3, 4), jnp.float32)})


@pytest.fixture
def held_encode(monkeypatch):
    """Write-behinds wait for the returned event before they encode."""
    from repro.checkpoint import archive

    gate = threading.Event()
    fill = archive.Archive.fill

    def held(*args):
        assert gate.wait(60)
        return fill(*args)

    monkeypatch.setattr(archive.Archive, "fill", held)
    yield gate
    gate.set()
    drain_io()


def test_a_step_after_persist_returns_does_not_reach_the_saved_version(tmp_path, held_encode):
    from repro.checkpoint import TrainerStateObject
    from repro.checkpoint.trainer_so import params_digest

    step = jax.jit(lambda p, o, b: (jax.tree_util.tree_map(lambda x: x + 1, p), o, 0.0),
                   donate_argnums=(0, 1))
    so = TrainerStateObject(tmp_path, _small_state, step)
    saved = params_digest(so.params)
    done = threading.Event()
    so.Persist(1, b"meta", done.set)
    so.params, so.opt_state, _ = so.step_fn(so.params, so.opt_state, None)
    assert params_digest(so.params) != saved
    held_encode.set()
    assert done.wait(60)
    assert so.Restore(1) == b"meta"
    assert params_digest(so.params) == saved


def test_a_second_persist_waits_for_the_first_write_behind(tmp_path, held_encode):
    from repro.checkpoint import TrainerStateObject

    so = TrainerStateObject(tmp_path, _small_state, step_fn=None)
    first, second = threading.Event(), threading.Event()
    so.Persist(1, b"one", first.set)
    t = threading.Thread(target=so.Persist, args=(2, b"two", second.set))
    t.start()
    t.join(0.5)
    assert t.is_alive() and not first.is_set()
    held_encode.set()
    t.join(60)
    assert first.wait(60) and second.wait(60)
    assert so.ListVersions() == [(1, b"one"), (2, b"two")]
    assert [step for step, _, _ in so.save_log] == [0, 0]


@pytest.mark.parametrize("fault, encoded", [("crash", 2), ("store_poisoned", 5)])
def test_a_store_poisoned_mid_encode_publishes_no_version(fault, encoded, tmp_path, monkeypatch):
    """A crash poisons the store and stops the encode at the next leaf; a
    store poisoned alone refuses the finished blob. Neither publishes."""
    from repro.checkpoint import TrainerStateObject, archive

    so = TrainerStateObject(
        tmp_path, lambda: ([np.full(4, i, np.float32) for i in range(5)], []), step_fn=None)
    placed = []
    place = archive._place

    def counted(*args):
        placed.append(1)
        if len(placed) == 2 and fault == "crash":
            so.on_crash()
        elif len(placed) == 2:
            so.store.poison()
        return place(*args)

    monkeypatch.setattr(archive, "_place", counted)
    done = threading.Event()
    so.Persist(1, b"meta", done.set)
    drain_io()
    assert len(placed) == encoded
    assert not done.is_set() and so.save_log == []
    assert so.ListVersions() == [] and list(tmp_path.iterdir()) == []


def test_the_store_writes_each_part_of_the_archive_once_it_is_final(tmp_path, monkeypatch):
    """The write follows the fill: with the fill held before its third
    leaf, the file being written already holds the first two entries, and
    nothing past what is final."""
    from repro.checkpoint import TrainerStateObject, archive
    from repro.core import state_object

    monkeypatch.setattr(state_object, "_WRITE_CHUNK", 256)
    leaves = [np.full(8192, i, np.float32) for i in range(4)]
    so = TrainerStateObject(tmp_path, lambda: (leaves, []), step_fn=None)
    meta, prefix = b"meta", so._header(None, True)
    blob = _savez_blob(prefix, leaves)
    want = len(meta).to_bytes(8, "little") + meta + blob
    # the payload's bytes final once two leaves are in place, in whole chunks
    final = len(prefix) + zipfile.ZipFile(io.BytesIO(blob[len(prefix):])).getinfo(
        "arr_2.npy").header_offset
    expect = 12 + final - final % 256
    gate, placed = threading.Event(), []
    place = archive._place

    def held(*args):
        placed.append(1)
        if len(placed) == 3:
            assert gate.wait(60)
        return place(*args)

    monkeypatch.setattr(archive, "_place", held)
    done = threading.Event()
    so.Persist(1, meta, done.set)
    try:
        deadline = time.monotonic() + 60
        while True:
            tmp = [p for p in tmp_path.iterdir() if p.suffix == ".tmp"]
            # the file object buffers up to io.DEFAULT_BUFFER_SIZE bytes
            if tmp and tmp[0].stat().st_size > expect - io.DEFAULT_BUFFER_SIZE:
                break
            assert time.monotonic() < deadline
            time.sleep(0.005)
        time.sleep(0.05)
        assert len(placed) == 3 and not done.is_set()
        written = tmp[0].read_bytes()
        assert len(written) <= expect and written == want[: len(written)]
    finally:
        gate.set()
    assert done.wait(60)
    assert (tmp_path / "v1.blob").read_bytes() == want


def test_back_to_back_saves_under_a_short_switch_interval(tmp_path, monkeypatch):
    """Fill and write hand each part over under frequent thread switches;
    a lost hand-over would write bytes not yet final, or hang."""
    import sys

    from repro.checkpoint import TrainerStateObject
    from repro.core import state_object

    monkeypatch.setattr(state_object, "_WRITE_CHUNK", 64)
    leaves = [np.arange(i, i + 37, dtype=np.float32) for i in range(40)]
    so = TrainerStateObject(tmp_path, lambda: (list(leaves), []), step_fn=None)
    meta = b"meta"
    want = len(meta).to_bytes(8, "little") + meta + _savez_blob(so._header(None, True), leaves)
    dones = [threading.Event() for _ in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for version, done in enumerate(dones, 1):
            so.Persist(version, meta, done.set)
        assert all(done.wait(60) for done in dones)
    finally:
        sys.setswitchinterval(interval)
    drain_io()
    for version in range(1, len(dones) + 1):
        assert (tmp_path / f"v{version}.blob").read_bytes() == want


def test_a_write_abandoned_by_its_payload_publishes_nothing(tmp_path, monkeypatch):
    from repro.core import state_object
    from repro.core.state_object import VersionStore

    monkeypatch.setattr(state_object, "_WRITE_CHUNK", 4)
    store = VersionStore(tmp_path)
    asked = []

    def ready(n):
        asked.append(n)
        return n <= 8

    with pytest.raises(RuntimeError, match="abandoned"):
        store.write(1, bytes(20), b"meta", ready=ready)
    assert asked == [4, 8, 12]
    assert store.list_versions() == [] and list(tmp_path.iterdir()) == []
    store.write(2, bytes(20), b"meta", ready=lambda n: True)
    assert store.read(2) == (bytes(20), b"meta")


def test_gradient_compression_error_feedback():
    from repro.optim import compress_gradients_int8, decompress_gradients_int8

    key = jax.random.key(0)
    grads = {"a": jax.random.normal(key, (64, 64)), "b": jax.random.normal(key, (8,))}
    ef = jax.tree_util.tree_map(lambda g: jnp.zeros_like(g, jnp.float32), grads)
    acc_true = jax.tree_util.tree_map(lambda g: jnp.zeros_like(g, jnp.float32), grads)
    acc_q = jax.tree_util.tree_map(lambda g: jnp.zeros_like(g, jnp.float32), grads)
    for i in range(20):
        codes, scales, ef = compress_gradients_int8(grads, ef)
        deq = decompress_gradients_int8(codes, scales)
        acc_true = jax.tree_util.tree_map(lambda a, g: a + g, acc_true, grads)
        acc_q = jax.tree_util.tree_map(lambda a, g: a + g, acc_q, deq)
    # error feedback keeps the accumulated quantized stream unbiased: the
    # residual is bounded by one quantization step, NOT O(n_steps)
    for k in grads:
        err = np.max(np.abs(np.asarray(acc_true[k]) - np.asarray(acc_q[k])))
        scale = float(np.max(np.abs(np.asarray(grads[k])))) / 127.0
        assert err <= 2.0 * scale + 1e-6, (k, err, scale)
