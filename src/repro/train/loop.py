"""Resilient training loop under DSE — the paper's durable-execution
abstraction applied to a JAX training job (DESIGN.md §2).

The driver composes three StateObjects:
    data  (stream cursor)  --header-->  trainer  --header-->  metrics

Every train step runs SPECULATIVELY: persistence happens in the background
at the group-commit cadence; failures roll the affected components back to
the consistent prefix and the driver resumes from the trainer's restored
step (control flow is part of persisted state). Externally-visible metrics
are barrier-gated. With a deterministic data pipeline, a run with failures
produces bit-identical parameters to a failure-free run — that is the
determinism test in tests/test_training.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

from ..checkpoint import DeltaCheckpointCodec, MetricsStateObject, TrainerStateObject
from ..core import DelayMessage, LocalCluster
from ..data import DataPipelineStateObject, SyntheticLMData
from ..models import init_params, param_descs
from ..models.config import ModelConfig
from ..optim import AdamWConfig, adamw_init
from ..launch.steps import make_train_step

#: how long the final metrics export may wait for the last saves to become
#: durable
FINAL_SAVE_TIMEOUT_S = 600.0


@dataclass
class TrainRunResult:
    steps_run: int
    final_step: int
    params_digest: str
    metrics: List[Tuple[int, float]]
    external_metrics: List[Tuple[int, float]]
    rollbacks: int
    checkpoint_bytes: int
    #: trainer step observed right after each recovery (the restored step)
    restored_to: List[int] = field(default_factory=list)
    #: (step, snapshot seconds, seconds until durable) per trainer save,
    #: host wall-clock timings
    saves: List[Tuple[int, float, float]] = field(default_factory=list)


def train_step_fn(cfg: ModelConfig, lr: float = 1e-3):
    """The loop's jitted train step: (params, opt, batch) -> (params, opt,
    loss), with full rematerialization. Params and optimizer state are
    donated: ``train_on`` replaces them with the outputs, and ``Persist``
    copies them to the host under the exclusive epoch, before any later step
    can consume them."""
    return jax.jit(make_train_step(cfg, AdamWConfig(lr=lr)), donate_argnums=(0, 1))


def init_train_state(cfg: ModelConfig, seed: int = 0):
    """Seeded float32 parameters and their AdamW state."""
    params = init_params(param_descs(cfg), jax.random.key(seed), dtype=jax.numpy.float32)
    return params, adamw_init(params)


def run_resilient_training(
    root: Path,
    cfg: ModelConfig,
    *,
    steps: int = 20,
    global_batch: int = 4,
    seq_len: int = 16,
    kill_trainer_at: Optional[int] = None,
    kill_data_at: Optional[int] = None,
    group_commit_interval: float = 0.02,
    use_delta_codec: bool = False,
    seed: int = 0,
    lr: float = 1e-3,
) -> TrainRunResult:
    data = SyntheticLMData(cfg.vocab_size, global_batch, seq_len, seed=seed)
    step_fn = train_step_fn(cfg, lr)
    init_state = lambda: init_train_state(cfg, seed)
    codec = DeltaCheckpointCodec(base_every=4) if use_delta_codec else None
    saves: List[Tuple[int, float, float]] = []

    with LocalCluster(root, group_commit_interval=group_commit_interval) as cluster:
        data_so = cluster.add(
            "data", lambda: DataPipelineStateObject(Path(root) / "data", data)
        )
        trainer = cluster.add(
            "trainer",
            lambda: TrainerStateObject(
                Path(root) / "trainer", init_state, step_fn, codec=codec, save_log=saves
            ),
        )
        metrics = cluster.add("metrics", lambda: MetricsStateObject(Path(root) / "metrics"))

        rollbacks = 0
        restored_to: List[int] = []
        steps_run = 0
        last_world = 0
        while True:
            trainer = cluster.get("trainer")
            data_so = cluster.get("data")
            metrics = cluster.get("metrics")
            if trainer.runtime.world > last_world:  # a recovery happened
                rollbacks += trainer.runtime.world - last_world
                last_world = trainer.runtime.world
                restored_to.append(trainer.current_step())
            t_step = trainer.current_step()
            if t_step >= steps:
                break

            try:
                if data_so.peek_cursor() != t_step:
                    data_so.seek(t_step)  # resync after rollback/restart
                    # reconcile metrics: a rollback may have dropped records
                    # for steps the trainer's restored state still covers (the
                    # paper's conservative over-rollback, §5.3); re-record
                    # from the trainer's own persisted loss history.
                    snap = trainer.history_snapshot()
                    if snap is not None:
                        history, hh = snap
                        have = {s for s, _ in metrics.records}
                        for s, l in history:
                            if s not in have:
                                metrics.record(s, l, hh)

                out = data_so.next_batch()
                if out is None:
                    continue
                step, tokens, hdr = out
                res = trainer.train_on(step, tokens, hdr)
                if res is None:
                    # stale cross-epoch message: let the refresher deliver
                    # the decision instead of spinning
                    cluster.refresh_all()
                    continue
                if isinstance(res, tuple) and res[0] == "resync":
                    continue
                loss, thdr = res
                steps_run += 1
                metrics.record(step, loss, thdr)
            except DelayMessage:
                # cross-epoch message (Def 4.3): let lagging components apply
                # pending decisions, then retry the iteration.
                cluster.refresh_all()
                continue

            if kill_trainer_at is not None and step + 1 == kill_trainer_at:
                cluster.kill("trainer")
                kill_trainer_at = None  # counted via the world watermark
            if kill_data_at is not None and step + 1 == kill_data_at:
                cluster.kill("data")
                kill_data_at = None

        # reconcile any metric dropped by a late rollback (the refresher
        # applies decisions asynchronously), make every member's last action
        # durable, then export only non-speculative metrics. The history read
        # is itself a trainer action, so it is taken only when a record is
        # missing, and each member saves only if an action ran since its last
        # save began: at full width a trainer save is 5 GB.
        trainer = cluster.get("trainer")
        metrics = cluster.get("metrics")
        have = {s for s, _ in metrics.records}
        if not have.issuperset(range(trainer.current_step())):
            snap = trainer.history_snapshot()
            if snap is not None:
                history, hh = snap
                for s, l in history:
                    if s not in have:
                        metrics.record(s, l, hh)
        for so_id in ("data", "trainer"):
            cluster.get(so_id).runtime.persist_if_dirty()
        # the barrier waits out a whole trainer save (~25 s at full width)
        external = metrics.flush_external(timeout=FINAL_SAVE_TIMEOUT_S)
        recorded = list(metrics.records)

        return TrainRunResult(
            steps_run=steps_run,
            final_step=trainer.current_step(),
            params_digest=trainer.params_digest(),
            metrics=recorded,
            external_metrics=external,
            rollbacks=rollbacks,
            checkpoint_bytes=trainer.bytes_written,
            restored_to=restored_to,
            saves=saves,
        )
