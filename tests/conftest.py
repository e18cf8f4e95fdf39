"""Shared fixtures: a minimal CounterStateObject (the paper's running
example, Fig. 3/4) used across protocol tests, and cluster factories.

NOTE: XLA_FLAGS / device-count manipulation is intentionally absent here —
smoke tests and benches must see the 1 real CPU device; only
``repro.launch.dryrun`` installs the 512-device placeholder flag.
"""
from __future__ import annotations

import threading
from pathlib import Path
from typing import Optional, Tuple

import pytest

from repro.core.clock import Clock, REAL_CLOCK
from repro.services.counter import CounterStateObject as CounterSO


@pytest.fixture
def cluster_factory(tmp_path):
    """Yields a factory building LocalClusters rooted under tmp_path."""
    from repro.core import LocalCluster

    made = []

    def make(name: str = "c0", **kw) -> LocalCluster:
        c = LocalCluster(tmp_path / name, **kw)
        made.append(c)
        return c

    yield make
    for c in made:
        c.shutdown()


def make_counter(tmp_path: Path, name: str, io_ms: float = 0.0):
    def factory() -> CounterSO:
        return CounterSO(tmp_path / f"so_{name}", io_ms=io_ms)

    return factory


def wait_committed(
    so, label: Optional[int], timeout: float = 5.0, clock: Clock = REAL_CLOCK
) -> bool:
    """Deadline-poll until the async Persist IO for ``label`` has committed
    (fixed sleeps race the IO thread on a loaded machine). Pass a SimClock to
    poll in virtual time under deterministic simulation."""
    if label is None:
        return True
    deadline = clock.now() + timeout
    while clock.now() < deadline:
        if so.runtime.stats()["committed"] >= label:
            return True
        clock.sleep(0.002)
    return False


def settle(
    predicate,
    cluster=None,
    timeout: float = 10.0,
    interval: float = 0.01,
    clock: Clock = REAL_CLOCK,
) -> bool:
    """Deadline-poll ``predicate``, optionally driving ``cluster`` refresh
    rounds each iteration. Clock-injected: under the real clock this is the
    usual anti-flake poll loop; under a SimClock the waits are virtual and
    the poll runs deterministically (``SimCluster.settle`` is its in-tree
    twin for scenario code)."""
    deadline = clock.now() + timeout
    while clock.now() < deadline:
        if cluster is not None:
            cluster.refresh_all()
        if predicate():
            return True
        clock.sleep(interval)
    return predicate()


def drain_io(timeout: float = 60.0) -> None:
    """Join every background Persist thread (``spawn_io`` names them
    ``...persist-io``), so a test sees its saves' writes ended."""
    for t in threading.enumerate():
        if t.name.endswith("persist-io"):
            t.join(timeout)
