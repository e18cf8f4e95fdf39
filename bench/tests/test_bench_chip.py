"""Tests of the chip benchmark that need no chip: the trace reduction, the
peaks table, the work counts, the references against the program at smoke
size, the harness driven end to end at rehearsal sizes, and the faults and
control that ``correct`` has to catch.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, trace, work  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from bench.reference import ssm_lm  # noqa: E402

CELLS = [w["name"] for w in harness.spec()["workloads"]]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def last_json(stdout: str):
    lines = [l for l in stdout.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


# --------------------------------------------------------------------------- #
# trace reduction, peaks, work counts                                         #
# --------------------------------------------------------------------------- #
def test_reduction_of_a_synthetic_trace():
    events = {
        "devices": {"/device:TPU:0": {
            "ops": [("%fusion.1", 1.0, 2.0), ("%fusion.2", 1.5, 2.5), ("%copy.3", 4.0, 4.5),
                    ("%fusion.1", 9.0, 12.0)],
            "modules": [("jit_train_step(1)", 1.0, 2.5), ("jit_train_step(1)", 4.0, 4.5),
                        ("jit_train_step(1)", 9.0, 12.0)],
        }},
        "spans": [("window", 0.5, 10.0), ("train_on", 0.8, 2.6), ("persist", 2.6, 8.0)],
    }
    r = trace.reduce_trace(events)
    assert r["window_s"] == pytest.approx(9.5)
    # busy: [1, 2.5] + [4, 4.5] + [9, 10] (the last op clipped to the window)
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["idle_gaps"][0] == ["persist", pytest.approx(4.5)]  # [4.5, 9]
    assert r["idle_gaps"][1] == ["persist", pytest.approx(1.5)]  # [2.5, 4]
    assert r["idle_gaps"][2] == ["train_on", pytest.approx(0.5)]  # [0.5, 1] overlaps it
    events["spans"] = [("window", 0.5, 10.0)]
    assert trace.reduce_trace(events)["idle_gaps"][0] == ["host idle", pytest.approx(4.5)]
    assert r["device_ops"][0] == ["%fusion.1", pytest.approx(2.0)]
    # modules count every execution that overlaps the window, timed whole
    assert trace.module_time(r, "jit_train_step") == (3, pytest.approx(5.0))
    m = r["modules"]["jit_train_step(1)"]
    assert (m["edge"], m["lead_s"], m["tail_s"]) == (1, pytest.approx(0.5), pytest.approx(-2.0))
    assert trace.idle_percent({"trace": r}) == pytest.approx(100 * (1 - 3.0 / 9.5))


def test_reduction_reads_nothing_without_device_operations():
    assert trace.reduce_trace({"devices": {}, "spans": [("window", 0.0, 1.0)]}) is None
    assert trace.reduce_trace({"devices": {}, "spans": []}) is None


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


@pytest.mark.parametrize("rehearsal,count", [(False, 421_709_312), (True, None)])
def test_work_counts_match_the_program(rehearsal, count):
    from repro.models import param_count, param_descs

    c = harness.config("mamba2-370m", rehearsal=rehearsal)
    n = work.param_count(c)
    assert count is None or n == count
    assert param_count(param_descs(harness.program_config(c))) == n
    leaves = jax.tree_util.tree_leaves(ssm_lm.layout(c), is_leaf=ssm_lm._is_leaf)
    assert sum(int(np.prod(s)) for s, _ in leaves) == n


def test_train_flops_and_decode_bytes():
    c = harness.config("mamba2-370m")
    per_token = work.train_flops_per_token(c)
    matmul = 48 * (1024 * (2 * 2048 + 2 * 128 + 32) + 2048 * 1024) + 1024 * 51200
    assert 6 * matmul < per_token < 1.25 * 6 * matmul  # SSD terms add under a quarter
    # every weight but the embedding table, one row of it, and the states
    weights = (421_709_312 - 51200 * 1024 + 1024) * 4
    states = 48 * 2 * (3 * (2048 + 2 * 128) + 32 * 64 * 128) * 4
    assert work.decode_bytes(c) == weights + states + 51200 * 4


def test_the_decode_reader_needs_the_decode_program_once_per_step():
    reader = harness.reader("decode_hbm_roofline")
    t = {"modules": {"jit__lambda(7)": {"count": 100, "seconds": 0.5},
                     "jit_argmax(3)": {"count": 100, "seconds": 0.01}}}
    run = {"driver": "serve", "trace": t, "conf": harness.config("mamba2-370m"),
           "device_kind": "TPU v5 lite", "decode_module": "jit__lambda", "traced_decodes": 100}
    want = 100 * work.decode_bytes(run["conf"]) / 819e9 / 0.005
    assert reader.read(run) == pytest.approx(want)
    for wrong in ({"decode_module": "jit_decode_step"}, {"traced_decodes": 99}):
        with pytest.raises(RuntimeError, match="executions"):
            reader.read({**run, **wrong})


def test_the_decode_program_is_named_by_the_sessions_own_step(tmp_path):
    from repro.models import init_params, param_descs
    from repro.train.serve import DecodeSessionStateObject

    from bench.drivers.serve import module_name

    cfg = harness.program_config(harness.config("mamba2-370m", rehearsal=True))
    params = init_params(param_descs(cfg), jax.random.key(0), jnp.float32)
    sess = DecodeSessionStateObject(tmp_path, cfg, params, max_len=8)
    assert module_name(sess) == "jit__lambda"


# --------------------------------------------------------------------------- #
# references against the program, at smoke size                              #
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def smoke():
    c = harness.config("mamba2-370m", rehearsal=True)
    cfg = harness.program_config(c)
    w = jax.jit(lambda s: ssm_lm.init_weights(c, s))(jnp.int32(11))
    return c, cfg, w


def test_reference_weights_follow_the_programs_recipe(smoke):
    from repro.models import init_params, param_descs

    c, cfg, w = smoke
    prog = init_params(param_descs(cfg), jax.random.key(11), jnp.float32)
    for a, b in zip(jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(w)):
        # the same draws; jit fuses the scaling, so the last bit may differ
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2.4e-7, atol=0)
    assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(w)


def test_reference_forward_matches_the_program(smoke):
    from repro.models import forward

    c, cfg, w = smoke
    tokens = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 32)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = forward(cfg, w, jnp.asarray(tokens))
        ref = ssm_lm.logits(w, jnp.asarray(tokens), c)
    # float32 on the CPU; the chunked SSD and the quadratic form sum in
    # different orders, so agreement is to float32 rounding of O(1) logits
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_reference_matches_the_programs_decode(smoke):
    from repro.models import cache_descs, decode_step
    from repro.models.params import is_desc

    c, cfg, w = smoke
    n = 24
    cache = jax.tree_util.tree_map(lambda d: jnp.zeros(d.shape, jnp.float32),
                                   cache_descs(cfg, batch=1, max_len=32), is_leaf=is_desc)
    step = jax.jit(lambda p, ca, t, i: decode_step(cfg, p, ca, t, i))
    tokens = np.random.default_rng(1).integers(0, c["vocab_size"], n).astype(np.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            lg, cache = step(w, cache, jnp.asarray([[tokens[i]]]), jnp.asarray(i, jnp.int32))
            out.append(np.asarray(lg[0, 0, : c["vocab_size"]]))
        ref = ssm_lm.serve_logits_fn(c)(w, tokens, 32)
    # the recurrent decode against the whole-sequence form: float32 rounding
    np.testing.assert_allclose(np.stack(out), ref, atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------------- #
# the harness, end to end at rehearsal sizes                                   #
# --------------------------------------------------------------------------- #
def run_cli(root: Path, cell: str, *extra, seed=2**31 + 17):
    cmd = [sys.executable, str(root / "bench" / "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", "2", "--trace", "0", *extra]
    return subprocess.run(cmd, cwd=root, env=ENV, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_in_rehearsal(cell, trace):
    p = run_cli(ROOT, cell, "--rehearsal", "--trace", trace)
    out = last_json(p.stdout)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"], p.stderr[-3000:]
    kind = "per_layer" if trace == "1" else "end_to_end"
    names = {m["name"] for m in harness.cell_metrics(cell, kind)}
    # a CPU run reads no device metric: only the host-clock ones appear
    host = {m["name"] for m in harness.cell_metrics(cell, kind) if m["source"] == "host_clock"}
    assert host <= set(out["metrics"]) <= names
    assert "busy_s" not in out["device"]
    assert list(out)[-1] == "checks"
    assert not harness.RUN_DIR.exists()


def test_without_the_flag_a_cpu_run_exits_nonzero():
    p = run_cli(ROOT, CELLS[0])
    assert p.returncode != 0
    assert last_json(p.stdout) is None


def test_without_the_program_a_run_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = run_cli(tmp_path, CELLS[0], "--rehearsal")
    assert p.returncode != 0
    assert last_json(p.stdout) is None


def test_a_new_cell_mix_config_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "mamba2-370m.json").read_text())
    conf["name"] = "mamba2-copy"
    (b / "configs" / "mamba2-copy.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "train.steady.json").read_text())
    mix["rehearsal"]["seq_len"] = 32
    (b / "traffic" / "train.short.json").write_text(json.dumps(mix))
    (b / "metrics" / "steps_done.py").write_text(
        "def read(run):\n    return run.get('steps')\n")
    spec = harness.spec()
    cell = "mamba2-copy.train.short"
    spec["configs"].append({**spec["configs"][0], "name": "mamba2-copy",
                            "file": "bench/configs/mamba2-copy.json"})
    spec["workloads"].append({"name": cell, "config": "mamba2-copy", "traffic": "train.short",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append(cell)
    spec["per_layer"].append({"name": "steps_done", "unit": "steps", "better": "higher",
                              "source": "host_clock", "layer": "driver",
                              "moves": "train_tokens_per_s", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for p, data in before.items():
        assert p.read_bytes() == data  # no file that was there changed

    p = run_cli(tmp_path, cell, "--rehearsal", "--trace", "1")
    out = last_json(p.stdout)
    assert p.returncode == 0 and out["correct"], p.stderr[-3000:]
    assert out["metrics"]["steps_done"]["value"] > 0
    assert "step_mfu" not in out["metrics"]  # not listed for this cell


# --------------------------------------------------------------------------- #
# what correct has to catch                                                    #
# --------------------------------------------------------------------------- #
def run_inprocess(cell: str, capsys, tmp_path, monkeypatch):
    from bench import run as bench_run

    monkeypatch.setattr(harness, "RUN_DIR", tmp_path / "run")
    rc = bench_run.main(["--workload", cell, "--seed", "123456789012", "--seconds", "1",
                         "--trace", "0", "--rehearsal"])
    assert rc == 0
    return last_json(capsys.readouterr().out)


def _unchanged_state_step(real):
    def make(cfg, lr=1e-3):
        from repro.launch.steps import make_train_step
        from repro.optim import AdamWConfig

        step = jax.jit(make_train_step(cfg, AdamWConfig(lr=lr)))

        def f(p, o, batch):
            _, _, loss = step(p, o, batch)
            return p, o, loss

        return f

    return make


def _half_batch_step(real):
    def make(cfg, lr=1e-3):
        step = real(cfg, lr)
        return lambda p, o, batch: step(p, o, {"tokens": batch["tokens"][: len(batch["tokens"]) // 2]})

    return make


@pytest.mark.parametrize("fault", [_unchanged_state_step, _half_batch_step])
@pytest.mark.parametrize("cell", [c for c in CELLS if ".train." in c])
def test_a_broken_train_step_is_not_correct(cell, fault, capsys, tmp_path, monkeypatch):
    import repro.train

    monkeypatch.setattr(repro.train, "train_step_fn", fault(repro.train.train_step_fn))
    out = run_inprocess(cell, capsys, tmp_path, monkeypatch)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", [c for c in CELLS if ".serve." in c])
def test_an_altered_token_is_not_correct(cell, capsys, tmp_path, monkeypatch):
    import repro.train.serve as sv

    from bench.control import token_fault

    monkeypatch.setattr(sv.DecodeSessionStateObject, "__init__",
                        token_fault(sv.DecodeSessionStateObject.__init__))
    out = run_inprocess(cell, capsys, tmp_path, monkeypatch)
    assert out["correct"] is False, out["checks"]


def test_the_train_control_is_not_correct():
    """The reference in bfloat16 in the program's place fails a limit."""
    from bench.drivers.train import compare_train
    from bench.tokens import UniformTokens

    c = harness.config("mamba2-370m", rehearsal=True)
    tr = harness.traffic("train.steady", rehearsal=True)
    src = UniformTokens(c["vocab_size"], tr["batch"], tr["seq_len"], 5)
    batches = [src.batch_at(s) for s in range(tr["check_steps"])]
    with jax.default_matmul_precision("highest"):
        ref = ssm_lm.train_readings(c, c["optimizer"], 5, batches)
    low = ssm_lm.train_readings(c, c["optimizer"], 5, batches, dtype=jnp.bfloat16)
    checks = harness.Checks(c["limits"]["train"])
    compare_train(checks, ref, low["losses"], low["grad"], low["change"], lambda *a: None)
    assert not checks.correct, checks.items


def test_the_serve_control_is_not_correct():
    """At every served position, the token the bfloat16 reference puts
    first lies further below the float32 reference's best than the limit
    allows. The smoke model (4 layers of width 64) is too shallow for
    bfloat16 to reorder its logits much, so this runs the reference alone
    at 8 layers of width 128 over two 512-token sessions."""
    from bench.drivers import serve

    c = dict(harness.config("mamba2-370m", rehearsal=True), d_model=128, n_layer=8,
             vocab_size=2048, vocab_padded=2048, headdim=32, d_state=32)
    rng = np.random.default_rng(6)
    sessions = {s: rng.integers(0, c["vocab_size"], 512).tolist() for s in ("a", "b")}
    turns = [{"sid": s, "start": 0, "tokens": t} for s, t in sessions.items()]
    gap, n = serve.served_gap(ssm_lm, c, 6, sessions, turns, dtype=jnp.bfloat16)
    assert n == 1024 and gap > c["limits"]["serve"]["logit_gap"], gap
