"""DSE-resilient training loop tests: the paper's core claim transplanted
to training — speculative execution past checkpoints with rollback recovery
is EQUIVALENT to failure-free execution (bit-identical parameters), while
external observers never see rolled-back state."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def test_snapshot_blob_opens_as_zip64_archive(tmp_path):
    """A full-width snapshot is over 4 GB, so its archive carries zip64
    records, whose offsets are absolute: the archive must start the body
    Restore loads. More than 65535 leaves force those records at a tiny size."""
    import io

    from repro.checkpoint import TrainerStateObject

    leaves = [np.full(2, i, np.float32) for i in range(66_000)]
    so = TrainerStateObject(tmp_path, lambda: (leaves, []), step_fn=None)
    hdr, body = so._split_blob(so._snapshot_blob(1))
    assert hdr["step"] == 0 and hdr["base"]
    archive = np.load(io.BytesIO(body))
    assert len(archive.files) == 66_000
    assert float(archive[archive.files[-1]][0]) == 65_999.0


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
