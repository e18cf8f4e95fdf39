"""Training cells: the program's DSE trainer, driven step by step in the
order of ``train/loop.py``.

One ``LocalCluster`` holds the program's ``data`` (``DataPipelineStateObject``
over the benchmark's token source), ``trainer`` (``TrainerStateObject``
around the loop's jitted ``train_step_fn``) and ``metrics``
(``MetricsStateObject``) members. A step is ``next_batch``, ``train_on``,
``record``, with the loop's resync after a rollback. Traffic parameters:

- ``batch``, ``seq_len``: the token source (``bench/tokens.py``);
- ``check_steps``: steps run in set-up, through the same calls, that the
  reference follows;
- ``save_every``: with a number, the window is whole cycles of that many
  steps and one ``persist_if_dirty()`` on every member, ending at the first
  cycle boundary after ``--seconds``; with null, steps until ``--seconds``;
- ``group_commit_s``: the cluster's group commit (set so none falls due);
- ``kill_after_window``: after the window and one more step, kill the
  trainer and time the resume to the end of the first step after it;
- ``trace_steps``: steps traced after the window when ``--trace 1`` (a
  cycle with ``save_every``).
"""
from __future__ import annotations

import gc
import shutil
import statistics
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import harness
from bench.tokens import UniformTokens
from bench.trace import capture, load_events, reduce_trace, span

MEMBERS = ("data", "trainer", "metrics")


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import MetricsStateObject, TrainerStateObject
    from repro.core import DelayMessage, LocalCluster
    from repro.data import DataPipelineStateObject
    from repro.optim import AdamWConfig
    from repro.train import init_train_state, train_step_fn

    c, tr, cfg = ctx.conf, ctx.traffic, ctx.cfg
    opt = c["optimizer"]
    prog_opt = AdamWConfig(lr=opt["lr"])
    for k in ("b1", "b2", "eps", "weight_decay", "grad_clip"):
        if getattr(prog_opt, k) != opt[k]:
            raise ValueError(f"the program's AdamW {k}={getattr(prog_opt, k)}, the file's {opt[k]}")
    B, S = tr["batch"], tr["seq_len"]
    source = UniformTokens(c["vocab_size"], B, S, ctx.seed)
    step_fn = train_step_fn(cfg, lr=opt["lr"])
    init = jax.jit(lambda s: init_train_state(cfg, s))
    seed = jnp.int32(ctx.seed32)
    saves: List[Tuple[int, float, float]] = []
    root = ctx.run_dir

    cluster = LocalCluster(root, group_commit_interval=tr["group_commit_s"])
    cluster.add("data", lambda: DataPipelineStateObject(root / "data", source))
    cluster.add("trainer", lambda: TrainerStateObject(
        root / "trainer", lambda: init(seed), step_fn, save_log=saves))
    cluster.add("metrics", lambda: MetricsStateObject(root / "metrics"))

    def one_step() -> Tuple[int, float]:
        """The loop body of ``run_resilient_training`` until one train step
        completes: resync after a rollback, next_batch, train_on, record."""
        while True:
            trainer, data, metrics = (cluster.get(m) for m in ("trainer", "data", "metrics"))
            t_step = trainer.current_step()
            try:
                if data.peek_cursor() != t_step:
                    data.seek(t_step)
                    snap = trainer.history_snapshot()
                    if snap is not None:
                        history, hh = snap
                        have = {s for s, _ in metrics.records}
                        for s, l in history:
                            if s not in have:
                                metrics.record(s, l, hh)
                with span("next_batch"):
                    out = data.next_batch()
                if out is None:
                    continue
                step, tokens, hdr = out
                with span("train_on"):
                    res = trainer.train_on(step, tokens, hdr)
                if res is None:
                    cluster.refresh_all()
                    continue
                if isinstance(res, tuple) and res[0] == "resync":
                    continue
                loss, thdr = res
                with span("record"):
                    metrics.record(step, loss, thdr)
                return step, loss
            except DelayMessage:
                cluster.refresh_all()

    losses: Dict[int, float] = {}
    #: (host time of the call, trainer step, seconds the trainer's save held
    #: the training loop: its snapshot under the exclusive epoch)
    persists: List[Tuple[float, int, float]] = []

    def persist() -> None:
        with span("persist"):
            t, step, stall = time.perf_counter(), cluster.get("trainer").current_step(), 0.0
            for m in MEMBERS:
                tm = time.perf_counter()
                cluster.get(m).runtime.persist_if_dirty()
                if m == "trainer":
                    stall = time.perf_counter() - tm
            persists.append((t, step, stall))

    # -- set-up: the first steps, which the reference follows ------------------
    norm_fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(t)])
    p0 = jax.device_get(cluster.get("trainer").params)
    grad_norms: Dict[str, float] = {}
    for i in range(tr["check_steps"]):
        step, loss = one_step()
        losses[step] = loss
        if i == 0:
            # the first gradient as the optimizer took it: m = (1 - b1) g
            m = cluster.get("trainer").opt_state["m"]
            paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(m)[0]]
            grad_norms = {p: float(n) / (1 - opt["b1"]) for p, n in zip(paths, norm_fn(m))}
    p_after = jax.device_get(cluster.get("trainer").params)
    change = harness.leaf_norms_host(jax.tree_util.tree_map(np.subtract, p_after, p0))
    del p0, p_after
    every = tr["save_every"]
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s: version 0 durable, {tr['check_steps']} steps, losses "
            f"{[losses[s] for s in sorted(losses)]}")

    # -- the window -------------------------------------------------------------
    def cycle() -> int:
        n = 0
        for _ in range(every):
            step, loss = one_step()
            losses.setdefault(step, loss)
            n += 1
        persist()
        return n

    t0 = time.perf_counter()
    steps = 0
    while True:
        if every:
            steps += cycle()
        else:
            step, loss = one_step()
            losses.setdefault(step, loss)
            steps += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    tokens_per_s = steps * B * S / window_s
    stalls = [p[2] for p in persists]
    ctx.log(f"window {window_s:.3f} s: {steps} steps, {tokens_per_s:.1f} tokens/s, "
            f"{len(persists)} saves holding the loop {stalls} s")

    reduced = None
    if ctx.trace:
        with capture(str(root / "trace")):
            if every:
                cycle()
            else:
                for _ in range(tr["trace_steps"]):
                    step, loss = one_step()
                    losses.setdefault(step, loss)
        reduced = reduce_trace(load_events(str(root / "trace")))

    # -- kill after the window, and the resume ---------------------------------
    checks = harness.Checks(c["limits"]["train"])
    resume_s: Optional[float] = None
    if tr["kill_after_window"]:
        # one more step first, so that whichever save the resume restores
        # (the last, if its write ended before the kill, or the one before),
        # the first step after it ran before the kill too
        step, loss = one_step()
        losses.setdefault(step, loss)
        t_kill = time.perf_counter()
        with span("kill_restore"):
            cluster.kill("trainer")
            step, loss = one_step()
        resume_s = time.perf_counter() - t_kill
        # a save is acknowledged once durable; 0.1 s lets its report reach
        # the coordinator (the cluster refreshes every 2 ms)
        by_step = {s: t for t, s, _ in persists}
        by_step.setdefault(0, 0.0)
        acked = [s for s, _snap, durable in saves
                 if s in by_step and by_step[s] + durable < t_kill - 0.1]
        ctx.log(f"resume {resume_s:.3f} s: restored to step {step}; saves acknowledged "
                f"before the kill at steps {sorted(set(acked))}; loss {loss!r}, "
                f"before the kill {losses.get(step)!r}")
        checks.add("acked_steps_lost", max(0, max(acked, default=0) - step))
        if step in losses:
            checks.add("resume_loss_gap", abs(loss - losses[step]))
        else:
            checks.fail("resume_loss_gap", f"step {step} never ran before the kill")
    peak = harness.memory_peak_bytes(jax)

    # -- free the program's state, then the reference ---------------------------
    cluster.kill("trainer", restart=False)
    cluster.shutdown()
    harness.drain_io()
    del cluster
    gc.collect()
    shutil.rmtree(root, ignore_errors=True)

    ref = ctx.ref
    t_ref = time.perf_counter()
    batches = [source.batch_at(s) for s in range(tr["check_steps"])]
    with jax.default_matmul_precision("highest"):
        r = ref.train_readings(c, opt, ctx.seed32, batches)
    compare_train(checks, r, [losses[s] for s in range(tr["check_steps"])], grad_norms, change,
                  ctx.log)
    ctx.log(f"reference: {tr['check_steps']} steps in {time.perf_counter() - t_ref:.1f} s, "
            f"losses {r['losses']}")

    e2e = {"setup_s": setup_s}
    e2e["ckpt_tokens_per_s" if every else "train_tokens_per_s"] = tokens_per_s
    if resume_s is not None:
        e2e["resume_s"] = resume_s
    return {
        "end_to_end": e2e,
        "attempted": steps,
        "failed": 0,
        "checks": checks,
        "memory_peak_bytes": peak,
        "trace": reduced,
        "readings": {
            "driver": "train", "conf": c, "batch": B, "seq_len": S,
            "save_stalls": stalls, "steps": steps,
            # train steps run while traced (a cycle with saves)
            "traced_steps": (every or tr["trace_steps"]) if ctx.trace else 0,
        },
    }


def compare_train(checks, r: Dict, losses: List[float], grad_norms: Dict[str, float],
                  change: Dict[str, float], log) -> None:
    """The number that decides a training cell's ``correct`` besides the
    resume's: ``change_norm_gap``, the weights' change over all the steps
    by the worst leaf, leaving out leaves whose gradient in the reference
    is under a thousandth of the median leaf's (round-off alone moves them
    under Adam).

    Each step's loss and the first gradient by the worst leaf are logged,
    not compared: on the chip the program, whose float32 matmuls run at the
    default precision, reads as far from the reference on them as the
    bfloat16 control does, and no fault reads ten times its largest sound
    reading (PERF.md gives the readings)."""
    gaps = [abs(a - b) for a, b in zip(losses, r["losses"])]
    grad, grad_leaf = harness.worst_leaf_gap(grad_norms, r["grad"])
    med = statistics.median(r["grad_raw"].values())
    keep = [k for k, g in r["grad_raw"].items() if g >= 1e-3 * med]
    change, change_leaf = harness.worst_leaf_gap(change, r["change"], keep)
    checks.add("change_norm_gap", change)
    log(f"not compared: loss gaps by step {gaps}; first gradient {grad} (leaf {grad_leaf}). "
        f"Worst change leaf {change_leaf}; leaves left out of the change (reference gradient "
        f"under 1e-3 of the median) {sorted(set(r['grad_raw']) - set(keep))}")
