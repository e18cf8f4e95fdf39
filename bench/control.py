"""Readings that set the limits of ``correct``; the benchmark's runs never
run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--fault F] [--seconds S]

Training cells, per seed, at the cell's sizes: the program's train step
(the loop's jitted ``train_step_fn``, called directly with the cell's
batches) for the reference's steps, and its numbers against the float32
reference; with ``--fault half_batch`` besides, the same with the step
given half of each batch (the mean over the rest); and the control: the reference
itself in bfloat16, in the program's place.

Serving cells, per seed: a run of the cell's driver for ``--seconds``
(``--fault token``: each decode step's token replaced by the one the
logits rank last) and its numbers, then the control: at every served
position, the reference's gap of the token that the bfloat16 reference
puts first.

Prints one JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def program_train_readings(jax, cfg, c, tr, seed: int, half: bool):
    import jax.numpy as jnp
    import numpy as np

    from bench import harness
    from bench.tokens import UniformTokens
    from repro.train import init_train_state, train_step_fn

    step_fn = train_step_fn(cfg, lr=c["optimizer"]["lr"])
    source = UniformTokens(c["vocab_size"], tr["batch"], tr["seq_len"], seed)
    p, o = jax.jit(lambda s: init_train_state(cfg, s))(jnp.int32(harness.seed32(seed)))
    p0 = jax.device_get(p)
    norm_fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(t)])
    losses, grad = [], {}
    for s in range(tr["check_steps"]):
        tokens = source.batch_at(s)
        if half:
            tokens = tokens[: len(tokens) // 2]
        p, o, loss = step_fn(p, o, {"tokens": tokens})
        losses.append(float(loss))
        if s == 0:
            paths = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(o["m"])[0]]
            grad = {k: float(n) / (1 - c["optimizer"]["b1"]) for k, n in zip(paths, norm_fn(o["m"]))}
    change = harness.leaf_norms_host(jax.tree_util.tree_map(np.subtract, jax.device_get(p), p0))
    del p, o, p0
    return {"losses": losses, "grad": grad, "change": change}


def numbers(limits, r, got):
    from bench import harness
    from bench.drivers.train import compare_train

    checks = harness.Checks(limits)
    compare_train(checks, r, got["losses"], got["grad"], got["change"], log)
    out = {k: v["value"] for k, v in checks.items.items()}
    out["loss_gaps_by_step"] = [abs(a - b) for a, b in zip(got["losses"], r["losses"])]
    out["grad_norm_gap"] = harness.worst_leaf_gap(got["grad"], r["grad"])[0]
    return out


def train(args, jax, cfg, c, tr, ref):
    import gc

    import jax.numpy as jnp

    from bench import harness
    from bench.tokens import UniformTokens

    limits = c["limits"]["train"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        source = UniformTokens(c["vocab_size"], tr["batch"], tr["seq_len"], seed)
        batches = [source.batch_at(s) for s in range(tr["check_steps"])]
        prog = program_train_readings(jax, cfg, c, tr, seed, False)
        gc.collect()
        fault = None
        if args.fault == "half_batch":
            fault = program_train_readings(jax, cfg, c, tr, seed, True)
            gc.collect()
        with jax.default_matmul_precision("highest"):
            r = ref.train_readings(c, c["optimizer"], harness.seed32(seed), batches)
        gc.collect()
        out = {"seed": seed, "program": numbers(limits, r, prog)}
        if fault is not None:
            out[args.fault] = numbers(limits, r, fault)
        if not args.no_control:
            low = ref.train_readings(c, c["optimizer"], harness.seed32(seed), batches, dtype=jnp.bfloat16)
            out["control_bf16"] = numbers(limits, r, low)
        out["losses"] = {"program": prog["losses"], "reference": r["losses"]}
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        gc.collect()


def token_fault(init):
    """A ``DecodeSessionStateObject.__init__`` whose session's decode step
    puts first the token its logits rank last: a token altered where it is
    produced."""
    import jax.numpy as jnp

    def faulty_init(self, *a, **k):
        init(self, *a, **k)
        step = self._step

        def altered(p, cache, t, i):
            logits, cache = step(p, cache, t, i)
            worst = jnp.argmin(logits[0, 0, : self.cfg.vocab_size])
            return logits.at[0, 0, worst].set(jnp.max(logits) + 1.0), cache

        self._step = altered

    return faulty_init


def serve(args, jax, cfg, c, tr, ref, cell):
    import jax.numpy as jnp

    from bench import harness
    from bench.drivers import serve as drv

    if args.fault == "token":
        import repro.train.serve as sv

        sv.DecodeSessionStateObject.__init__ = token_fault(sv.DecodeSessionStateObject.__init__)
    for seed in args.seeds:
        ctx = SimpleNamespace(
            cell=cell, conf=c, traffic=tr, cfg=cfg, ref=ref, seed=seed, seed32=harness.seed32(seed),
            seconds=args.seconds, trace=False, rehearsal=args.rehearsal, run_dir=harness.RUN_DIR,
            t_start=time.perf_counter(), log=log)
        res = drv.run(ctx)
        out = {"seed": seed, "program" if args.fault == "none" else args.fault:
               {k: v["value"] for k, v in res["checks"].items.items()},
               "turns": res["attempted"]}
        if not args.no_control:
            rd = res["readings"]
            gap, n = drv.served_gap(ref, c, ctx.seed32, rd["sessions"], rd["window_turns"],
                                    dtype=jnp.bfloat16)
            out["control_bf16"] = {"logit_gap": gap, "positions": n}
        print(json.dumps(out), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--fault", default="none", choices=("none", "half_batch", "token"))
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    from bench import harness
    from bench.run import setup_jax

    cell = harness.cell(args.workload)
    c = harness.config(cell["config"], args.rehearsal)
    tr = harness.traffic(cell["traffic"], args.rehearsal)
    jax = setup_jax(args.rehearsal)
    if jax.devices()[0].platform != "tpu" and not args.rehearsal:
        sys.exit("bench/control.py: needs a TPU")
    cfg, ref = harness.program_config(c), harness.reference(c)
    if tr["driver"] == "train":
        train(args, jax, cfg, c, tr, ref)
    else:
        serve(args, jax, cfg, c, tr, ref, cell)


if __name__ == "__main__":
    main()
