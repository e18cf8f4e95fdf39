"""Speculative serving loop: derived-state (KV cache) recovery. A crashed
session restores its durable token prefix and REBUILDS the cache by
replay; continued greedy decoding is deterministic, so the final durable
stream equals the failure-free stream."""
from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import CrashedError, LocalCluster
from repro.models import init_params, param_descs
from repro.train.serve import DecodeSessionStateObject, run_speculative_serving

CFG = get_config("gemma_2b", smoke=True)


@pytest.fixture(scope="module")
def params():
    return init_params(param_descs(CFG), jax.random.key(0), jnp.float32)


def test_serving_generates_and_exports_durable(tmp_path, params):
    res = run_speculative_serving(tmp_path / "s", CFG, params, n_tokens=8)
    assert res.tokens_generated == 8
    assert res.durable_tokens[: res.tokens_generated]  # barrier-gated export


def test_serving_failure_equals_failure_free(tmp_path, params):
    base = run_speculative_serving(tmp_path / "b", CFG, params, n_tokens=10)
    inj = run_speculative_serving(
        tmp_path / "i", CFG, params, n_tokens=10, kill_at=5
    )
    assert inj.rollbacks == 1
    # derived-state recovery: same deterministic token stream
    assert inj.durable_tokens == base.durable_tokens


def _generate_in_thread(sess, n):
    """Run ``sess.generate(n)`` on a thread; its result or exception lands
    in the returned dict once the thread is joined."""
    out = {}

    def run():
        try:
            out["tokens"] = sess.generate(n)
        except Exception as e:  # noqa: BLE001 -- handed to the test
            out["error"] = e

    t = threading.Thread(target=run)
    t.start()
    return t, out


def test_a_kill_during_a_step_ends_generate_with_crashed_error(tmp_path, params):
    """A session killed while another thread is inside ``generate``: the
    kill lets go of the weights and caches only once the step in flight has
    ended, and that ``generate`` ends in ``CrashedError``, not in a step
    over released arrays."""
    with LocalCluster(tmp_path, group_commit_interval=0.01) as cluster:
        sess = cluster.add("s", lambda: DecodeSessionStateObject(tmp_path / "s", CFG, params))
        entered, go = threading.Event(), threading.Event()
        step = sess._step

        def held_step(*a):
            entered.set()
            assert go.wait(30)
            return step(*a)

        sess._step = held_step
        gen, out = _generate_in_thread(sess, 4)
        assert entered.wait(30)
        kill = threading.Thread(target=cluster.kill, args=("s",))
        kill.start()
        deadline = time.monotonic() + 30
        while not sess.runtime._dead:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        time.sleep(0.2)
        assert kill.is_alive() and sess.params is not None  # the release waits for the step
        go.set()
        gen.join(30)
        kill.join(30)
        assert not gen.is_alive() and not kill.is_alive()
        assert isinstance(out.get("error"), CrashedError), out
        assert sess.params is None and sess._cache is None  # the step did not put its cache back
        assert len(cluster.get("s").generate(2)) == 2  # the replacement serves


def test_a_generate_that_waited_for_the_release_raises_crashed_error(tmp_path, params):
    """A ``generate`` whose action began only after the session let go of
    its weights (it waited for the shared epoch while the release held the
    exclusive one) raises ``CrashedError``, as does a ``Restore`` then."""
    with LocalCluster(tmp_path, group_commit_interval=0.01) as cluster:
        sess = cluster.add("s", lambda: DecodeSessionStateObject(tmp_path / "s", CFG, params))
        with sess.runtime.exclusive_epoch():
            gen, out = _generate_in_thread(sess, 4)
            time.sleep(0.2)
            assert gen.is_alive()  # held at the action's start
            sess._cache = sess.params = None
        gen.join(30)
        assert isinstance(out.get("error"), CrashedError), out
        assert sess.tokens == []
        with pytest.raises(CrashedError):
            sess.Restore(0)
