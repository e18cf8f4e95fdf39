"""Serving cells: the program's decode sessions behind the speculation
barrier, one closed-loop client thread per session.

One ``LocalCluster`` holds ``sessions`` members, each a
``DecodeSessionStateObject`` over the same weights (made on the device from
the seed in one jitted call). A turn is one tool call: ``generate(L)``, then
``stream_durable`` returning the session's tokens once they are durable; the
client sends its next turn when that returns, with no think time. Traffic
parameters:

- ``sessions``: clients, one session each;
- ``turn_tokens``: [lo, hi, k]; the turn lengths are k evenly spaced
  lengths from lo to hi. A client sends rounds: each round is its own
  seeded permutation of the k lengths, so every seed sends the same set of
  lengths, in another order;
- ``max_len``: a session's cache length; a turn that would overflow it goes
  to a fresh session in its place;
- ``group_commit_s``: the cluster's group commit;
- ``release_timeout_s``: how long ``stream_durable`` may wait;
- ``trace_seconds``: how long the clients run traced after the window when
  ``--trace 1`` (each stops after the turn that ends past it).

A client stops at the end of the first round that ends after ``--seconds``
(every round is whole, so every run times the same mix); the window ends
when the last client stops.
"""
from __future__ import annotations

import gc
import shutil
import threading
import time
from typing import Dict, List

import numpy as np

from bench import harness
from bench.trace import capture, load_events, reduce_trace, span


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.core import LocalCluster
    from repro.models import init_params, param_descs
    from repro.train.serve import DecodeSessionStateObject

    c, tr, cfg = ctx.conf, ctx.traffic, ctx.cfg
    n, max_len = tr["sessions"], tr["max_len"]
    lo, hi, k = tr["turn_tokens"]
    lengths = np.rint(np.linspace(lo, hi, k)).astype(int)
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed % 2**64, 1]))
    orders = [rng.permutation(lengths) for _ in range(n)]
    root = ctx.run_dir

    params = jax.jit(lambda s: init_params(param_descs(cfg), jax.random.key(s), jnp.float32))(
        jnp.int32(ctx.seed32))
    cluster = LocalCluster(root, group_commit_interval=tr["group_commit_s"])
    lock = threading.Lock()
    sids: List[str] = []

    def add(sid: str):
        return cluster.add(sid, lambda: DecodeSessionStateObject(
            root / sid, cfg, params, max_len=max_len))

    # set-up: every session decodes and releases one token, which compiles
    # (the first) or loads (the others) the decode step
    for i in range(n):
        sids.append(f"s{i}")
        sess = add(sids[i])
        sess.generate(1)
        sess.stream_durable(timeout=tr["release_timeout_s"])
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s: {n} sessions, one token each")

    turns: List[Dict] = []
    ended: Dict[str, List[int]] = {}  # tokens of sessions replaced on overflow

    def client(i: int, stop_at: float, whole_rounds: bool) -> None:
        while time.perf_counter() < stop_at:
            for L in orders[i]:
                turn(i, int(L))
                if not whole_rounds and time.perf_counter() >= stop_at:
                    return

    def turn(i: int, L: int) -> None:
        sess = cluster.get(sids[i])
        if len(sess.tokens) + L > max_len:
            with lock:
                ended[sids[i]] = list(sess.tokens)
                sids[i] = f"s{i}.{len(ended)}"
            sess = add(sids[i])
        start = len(sess.tokens)
        ta = time.perf_counter()
        with span("generate"):
            out = sess.generate(L)
        tb = time.perf_counter()
        with span("stream_durable"):
            rel = sess.stream_durable(timeout=tr["release_timeout_s"])
        tc = time.perf_counter()
        ok = out is not None and rel is not None and len(out) == L
        with lock:
            turns.append({
                "sid": sids[i], "start": start, "tokens": out, "t0": ta, "t1": tb, "t2": tc,
                "ok": ok, "released": None if rel is None else rel[start:start + L],
            })

    def drive(seconds: float, whole_rounds: bool = True) -> float:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i, t0 + seconds, whole_rounds),
                                    name=f"client-{i}") for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    window_s = drive(ctx.seconds)
    window = list(turns)
    lat = [t["t2"] - t["t0"] for t in window]
    lags = [t["t2"] - t["t1"] for t in window if t["ok"]]
    served = sum(len(t["tokens"] or ()) for t in window)
    ctx.log(f"window {window_s:.3f} s: {len(window)} turns, {served} tokens "
            f"({served / window_s:.1f} tokens/s), turn p50 {harness.p_quantile(lat, 0.5) * 1e3:.1f} ms "
            f"p90 {harness.p_quantile(lat, 0.9) * 1e3:.1f} ms")

    reduced, decode_module = None, None
    if ctx.trace:
        decode_module = module_name(cluster.get(sids[0]))
        with capture(str(root / "trace")):
            drive(tr["trace_seconds"], whole_rounds=False)
        reduced = reduce_trace(load_events(str(root / "trace")))
    traced = turns[len(window):]

    peak = harness.memory_peak_bytes(jax)
    final = dict(ended)
    for sid in cluster.members():
        final[sid] = list(cluster.get(sid).tokens)

    checks = harness.Checks(c["limits"]["serve"])
    # the barrier: every released token is the token computed
    mismatched = sum(
        sum(a != b for a, b in zip(t["released"], t["tokens"])) + abs(len(t["released"]) - len(t["tokens"]))
        for t in turns if t["ok"])
    checks.add("released_mismatch", mismatched)

    for sid in cluster.members():
        cluster.kill(sid, restart=False)
    cluster.shutdown()
    harness.drain_io()
    del cluster, params
    gc.collect()
    shutil.rmtree(root, ignore_errors=True)

    t_ref = time.perf_counter()
    gap, positions = served_gap(ctx.ref, c, ctx.seed32, final, window)
    checks.add("logit_gap", gap)
    ctx.log(f"reference: {positions} served tokens of {len(final)} sessions in "
            f"{time.perf_counter() - t_ref:.1f} s")

    return {
        "end_to_end": {"setup_s": setup_s, "turn_p90_ms": harness.p_quantile(lat, 0.9) * 1e3},
        "attempted": len(window),
        "failed": sum(not t["ok"] for t in window),
        "checks": checks,
        "memory_peak_bytes": peak,
        "trace": reduced,
        "readings": {
            "driver": "serve", "conf": c, "release_lags": lags,
            "sessions": final,
            "window_turns": [{"sid": t["sid"], "start": t["start"], "tokens": t["tokens"]}
                             for t in window],
            # the decode program, and how many times the clients ran it traced
            "decode_module": decode_module,
            "traced_decodes": sum(len(t["tokens"] or ()) for t in traced),
        },
    }


def module_name(sess) -> str:
    """The name the device trace gives the session's decode program: the
    module name of the program's own jitted step, lowered at its shapes."""
    import jax.numpy as jnp

    lowered = sess._step.lower(sess.params, sess._cache, jnp.zeros((1, 1), jnp.int32),
                               jnp.asarray(0, jnp.int32))
    return lowered.compiler_ir("stablehlo").operation.attributes["sym_name"].value


def served_gap(ref, c: Dict, seed32: int, sessions: Dict[str, List[int]], turns: List[Dict],
               dtype=None):
    """Widest gap by which a token served in ``turns`` lies below the best
    logit of the reference, run once over each session's tokens (inputs:
    0 then the tokens before it). With ``dtype``, the control: the gap of
    the token that the reference in that precision puts first."""
    import jax
    import jax.numpy as jnp

    want: Dict[str, List[int]] = {}
    for t in turns:
        if t["tokens"]:
            want.setdefault(t["sid"], []).extend(range(t["start"], t["start"] + len(t["tokens"])))
    longest = max(len(sessions[s]) for s in want)
    pad = -(-longest // 512) * 512
    inputs = {s: np.asarray([0] + sessions[s][:-1]) for s in want}
    run = ref.serve_logits_fn(c)
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda s: ref.init_weights(c, s))(jnp.int32(seed32))
        full = {s: run(w, inputs[s], pad) for s in want}
    picked = {s: np.asarray(sessions[s]) for s in want}
    if dtype is not None:
        del w
        w = jax.jit(lambda s: ref.init_weights(c, s, dtype))(jnp.int32(seed32))
        picked = {s: run(w, inputs[s], pad).argmax(-1) for s in want}
    worst, count = 0.0, 0
    for s, pos in want.items():
        lg = full[s][pos]
        tok = picked[s][pos]
        worst = max(worst, float(np.max(lg.max(-1) - lg[np.arange(len(pos)), tok])))
        count += len(pos)
    return worst, count
