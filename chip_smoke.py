"""Chip smoke: drive the DSE training and serving paths once on one TPU, at
mamba2-370m's published widths (48 layers, 421.7M parameters, weights made
from a seed), and check what comes out.

    python chip_smoke.py

Phases, in one process, the short ones first:
  reference  the step program's memory analysis, then the loop's jitted
             train step applied STEPS times with no DSE and no saves: the
             failure-free losses and parameter digest.
  kernel     delta_encode / delta_decode compiled for the chip over the
             flattened parameters of step 0 against those of step 1,
             compared with kernels/ref.py.
  serve      run_speculative_serving twice, failure-free and with the
             session killed mid-stream. The durable token streams must be
             equal.
  train      run_resilient_training twice: failure-free, and with the
             trainer killed after a save and resumed by Restore from that
             save. Both runs' losses and digests must equal each other's
             and the reference's.

A full-width save writes 5.06 GB to disk, so the failure-free run saves only
at Connect and at the end, and the killed run about five times.

Exits non-zero before any phase unless JAX's first device is a TPU. With
CHIP_SMOKE_REHEARSAL=1 it runs the same phases on the CPU at the smoke
config, kernels interpreted, to check the control flow without a chip.
Timings printed are host wall-clock seconds. The last line of stdout is one
JSON object naming the device.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit("chip_smoke.py: src/repro not found next to this script")
sys.path.insert(0, str(ROOT / "src"))

REHEARSAL = os.environ.get("CHIP_SMOKE_REHEARSAL") == "1"
SEED = 0
BATCH, SEQ = (2, 32) if REHEARSAL else (4, 1024)
STEPS, KILL_AT = 3, 3
# The failure-free run's group commit never falls due, so it saves twice:
# at Connect and at the end.
FREE_GROUP_COMMIT_S = 3600.0
# The killed run commits as soon as state is dirty, saving the trainer after
# every step, so which save is durable at the kill follows from the step
# count, not from wall time: a save waits for the previous save's write to
# end before it copies the state, so the save after step 0 is durable before
# the save after step 1 is taken, and killing after step 2 resumes from
# step 1.
KILLED_GROUP_COMMIT_S = 0.0
SERVE_TOKENS, SERVE_KILL_AT = 16, 8
RUN_DIR = ROOT / ".chip_smoke"


def log(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke.py: check failed: {what}")


def drain_io() -> None:
    """Wait for background Persist writes, so a run's files are complete
    before its directory is removed."""
    for t in threading.enumerate():
        if t.name.endswith("persist-io"):  # StateObject.spawn_io threads
            t.join(timeout=300)
            check(not t.is_alive(), f"{t.name} still writing after 300 s")


def timed(name: str, phase, *args):
    t0 = time.perf_counter()
    out = phase(*args)
    gc.collect()  # dead incarnations hold device state in reference cycles
    log(f"phase {name} passed in {time.perf_counter() - t0:.1f} s (host)")
    return out


def phase_reference(jax, cfg) -> dict:
    import jax.numpy as jnp
    from repro.checkpoint.delta import _flatten, _pad_blocks
    from repro.checkpoint.trainer_so import params_digest
    from repro.data import SyntheticLMData
    from repro.train import init_train_state, train_step_fn

    step_fn = train_step_fn(cfg)
    state = jax.eval_shape(lambda: init_train_state(cfg, SEED))
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, SEQ + 1), jnp.int32)}
    t0 = time.perf_counter()
    mem = step_fn.lower(*state, batch).compile().memory_analysis()
    log(f"[reference] step program at batch {BATCH}x{SEQ}: compiled in "
        f"{time.perf_counter() - t0:.1f} s (host, one-off); bytes: arguments "
        f"{mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}, "
        f"aliased {mem.alias_size_in_bytes}, temporaries {mem.temp_size_in_bytes}")

    data = SyntheticLMData(cfg.vocab_size, BATCH, SEQ, seed=SEED)
    params, opt = init_train_state(cfg, SEED)
    blocks, losses = [], []
    for step in range(STEPS):
        if step < 2:  # the kernel phase's inputs: parameters of steps 0 and 1
            blocks.append(_pad_blocks(_flatten(params)[0]))
        params, opt, loss = step_fn(params, opt, {"tokens": data.batch_at(step)})
        losses.append(float(loss))
    digest = params_digest(params)
    log(f"[reference] {STEPS} steps without DSE: losses {losses} digest {digest}")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss in {losses}")
    return {"losses": losses, "digest": digest, "blocks": blocks}


def phase_kernel(jax, ref_run: dict) -> None:
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    prev, new = (jax.device_put(b) for b in ref_run.pop("blocks"))
    log(f"[kernel] parameters of steps 0 and 1 as {new.shape[0]} blocks of {new.shape[1]}")
    encode = ops.delta_encode.lower(new, prev).compile()
    codes, scales = encode(new, prev)
    decode = ops.delta_decode.lower(codes, scales, prev, dtype=jnp.float32).compile()
    if not REHEARSAL:
        for name, exe in (("delta_encode", encode), ("delta_decode", decode)):
            check("tpu_custom_call" in exe.as_text(), f"{name} is not a Mosaic kernel")
        log("[kernel] delta_encode and delta_decode compiled to tpu_custom_call")
    dec = decode(codes, scales, prev)

    codes_r, scales_r = jax.jit(ref.delta_encode_ref)(new, prev)
    diff = jnp.abs(codes.astype(jnp.int32) - codes_r.astype(jnp.int32))
    code_max, code_frac = int(jnp.max(diff)), float(jnp.mean(diff > 0))
    scale_rel = float(jnp.max(jnp.abs(scales - scales_r) / scales_r))
    # per block: decode error within 0.51 of the block's scale (+1e-6 for
    # the float32 rounding of prev + delta)
    err = jnp.max(jnp.abs(dec - new), axis=1)
    err_ratio = float(jnp.max(err / (scales * 0.51 + 1e-6)))
    log(f"[kernel] vs reference: codes differ by at most {code_max} "
        f"(in {code_frac:.2e} of entries), scales by rel {scale_rel:.2e}; "
        f"decode error at most {err_ratio:.4f} of its bound")
    check(code_max <= 1 and code_frac < 0.02, "codes differ from the reference")
    check(scale_rel <= 1e-6, "scales differ from the reference")
    check(err_ratio <= 1.0, "decode error exceeds 0.51 of a scale")


def phase_serve(jax, cfg) -> None:
    import jax.numpy as jnp
    from repro.models import init_params, param_descs
    from repro.train.serve import run_speculative_serving

    params = init_params(param_descs(cfg), jax.random.key(SEED), jnp.float32)
    runs = {}
    for name, kill in (("failure-free", None), ("killed", SERVE_KILL_AT)):
        t0 = time.perf_counter()
        res = run_speculative_serving(
            RUN_DIR / f"serve-{name}", cfg, params, n_tokens=SERVE_TOKENS, kill_at=kill
        )
        drain_io()
        runs[name] = res
        log(f"[serve] {name}: kill_at={kill} rollbacks={res.rollbacks} "
            f"durable tokens {res.durable_tokens} wall {time.perf_counter() - t0:.1f} s (host)")
    free, killed = runs["failure-free"], runs["killed"]
    check(len(free.durable_tokens) == SERVE_TOKENS,
          f"{len(free.durable_tokens)} durable tokens, {SERVE_TOKENS} asked for")
    check(killed.rollbacks == 1, f"killed session rolled back {killed.rollbacks} times")
    check(killed.durable_tokens == free.durable_tokens,
          "durable tokens differ after the kill")
    log("[serve] ok: the killed session's durable tokens equal the failure-free ones")


def train_run(cfg, name: str, kill_at, group_commit_s: float):
    from repro.train import run_resilient_training

    t0 = time.perf_counter()
    res = run_resilient_training(
        RUN_DIR / f"train-{name}", cfg, steps=STEPS, global_batch=BATCH,
        seq_len=SEQ, kill_trainer_at=kill_at, group_commit_interval=group_commit_s,
        seed=SEED,
    )
    drain_io()
    shutil.rmtree(RUN_DIR / f"train-{name}")  # 5 GB a save: free the disk now
    losses = [l for _, l in sorted(res.external_metrics)]
    log(f"[train] {name}: kill_trainer_at={kill_at} group_commit={group_commit_s} s "
        f"final_step={res.final_step} rollbacks={res.rollbacks} "
        f"restored_to={res.restored_to} digest={res.params_digest} "
        f"wall {time.perf_counter() - t0:.1f} s (host)")
    log(f"[train] {name}: losses {losses}")
    for step, snap_s, durable_s in res.saves:
        log(f"[train] {name}: save at step {step}: snapshot {snap_s:.2f} s, "
            f"durable after {durable_s:.2f} s (host wall-clock)")
    log(f"[train] {name}: {len(res.saves)} saves durable; the last trainer "
        f"incarnation wrote {res.checkpoint_bytes} bytes")
    check(res.final_step == STEPS, f"{name} run ended at step {res.final_step}")
    check(sorted(s for s, _ in res.external_metrics) == list(range(STEPS)),
          f"{name} run exported metrics for steps {res.external_metrics}")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss in {losses}")
    return res, losses


def phase_train(jax, cfg, ref_run: dict) -> None:
    free, free_losses = train_run(cfg, "failure-free", None, FREE_GROUP_COMMIT_S)
    check(free.rollbacks == 0, f"failure-free run rolled back {free.rollbacks} times")
    killed, killed_losses = train_run(cfg, "killed", KILL_AT, KILLED_GROUP_COMMIT_S)
    check(killed.rollbacks >= 1, "the kill did not roll the trainer back")
    check(bool(killed.restored_to) and min(killed.restored_to) > 0,
          f"resumed from step {killed.restored_to}: no save after step 0 was "
          "durable before the kill")
    check(killed.params_digest == free.params_digest,
          f"killed run's digest {killed.params_digest}, failure-free {free.params_digest}")
    check(killed_losses == free_losses,
          f"killed run's losses {killed_losses}, failure-free {free_losses}")
    check(free.params_digest == ref_run["digest"] and free_losses == ref_run["losses"],
          f"DSE runs end at {free.params_digest} with losses {free_losses}; "
          f"the plain step loop at {ref_run['digest']} with {ref_run['losses']}")
    log(f"[train] ok: the killed run resumed from step {killed.restored_to} by Restore "
        f"and ended at the failure-free digest {free.params_digest}, which the "
        "plain step loop reaches too")


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not REHEARSAL:
        sys.exit(f"chip_smoke.py: needs a TPU; JAX's first device is {dev.platform}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
        + (" (rehearsal: smoke config, kernels interpreted)" if REHEARSAL else ""))

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import param_count, param_descs

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config("mamba2_370m", smoke=REHEARSAL)
    log(f"model: {cfg.name}, {cfg.num_layers} layers, d_model {cfg.d_model}, vocab "
        f"{cfg.vocab_size}, {param_count(param_descs(cfg)) / 1e6:.1f}M parameters")

    shutil.rmtree(RUN_DIR, ignore_errors=True)
    ref_run = timed("reference", phase_reference, jax, cfg)
    timed("kernel", phase_kernel, jax, ref_run)
    timed("serve", phase_serve, jax, cfg)
    timed("train", phase_train, jax, cfg, ref_run)
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
