"""Protocol-level tests for the libDSE core (paper §3–§4).

Covers: dependency-graph fixpoints, commit ordering (both relabel and
paper-literal strict modes), speculative rollback + message discard,
skip-rollback mitigation (§5.3), sthreads + barriers, the recovery
partition rule across failure epochs, and coordinator failure/recovery.
"""
from __future__ import annotations

import threading
import time

import pytest

from repro.core import (
    DelayMessage,
    DependencyGraph,
    Header,
    RollbackDecision,
    RolledBackError,
    Vertex,
)

from conftest import CounterSO, make_counter, wait_committed


# --------------------------------------------------------------------------- #
# dependency graph fixpoints                                                   #
# --------------------------------------------------------------------------- #
class TestGraph:
    def test_boundary_simple_chain(self):
        g = DependencyGraph()
        g.report_persistent("A", 0, [])
        g.report_persistent("B", 0, [])
        g.report_persistent("A", 1, [])
        g.report_persistent("B", 1, [("A", 1)])
        assert g.recoverable_boundary() == {"A": 1, "B": 1}

    def test_boundary_dangling_dep_cuts_consumer(self):
        g = DependencyGraph()
        g.report_persistent("A", 0, [])
        g.report_persistent("B", 0, [])
        # B@1 depends on A@1 which is NOT persisted yet => B@1 outside boundary
        g.report_persistent("B", 1, [("A", 1)])
        b = g.recoverable_boundary()
        assert b["B"] == 0 and b["A"] == 0
        # once A@1 becomes durable the boundary catches up
        g.report_persistent("A", 1, [])
        assert g.recoverable_boundary() == {"A": 1, "B": 1}

    def test_boundary_transitive_cut(self):
        g = DependencyGraph()
        for so in "ABC":
            g.report_persistent(so, 0, [])
        g.report_persistent("B", 2, [("A", 2)])  # A@2 missing
        g.report_persistent("C", 3, [("B", 2)])
        b = g.recoverable_boundary()
        # watermark cuts exclude B@2 and C@3; snapped to loadable labels = v0
        assert b["B"] < 2 and b["C"] < 3
        assert g.snap_to_labels(b) == {"A": 0, "B": 0, "C": 0}

    def test_boundary_cycle_is_fine(self):
        # Vertices capture many transitions => cycles possible (paper §4.2).
        g = DependencyGraph()
        g.report_persistent("A", 1, [("B", 1)])
        g.report_persistent("B", 1, [("A", 1)])
        assert g.recoverable_boundary() == {"A": 1, "B": 1}

    def test_rollback_targets(self):
        g = DependencyGraph()
        for so in "ABC":
            g.report_persistent(so, 0, [])
        g.report_persistent("A", 1, [])
        g.report_persistent("A", 2, [])
        g.report_persistent("B", 2, [("A", 2)])
        g.report_persistent("C", 2, [("B", 2)])
        # A fails having lost version 2 (survived only up to 1):
        t = g.rollback_targets("A", 1)
        assert t["A"] == 1
        assert t["B"] == 0  # B@2 depended on lost A@2
        assert t["C"] == 0  # transitively
        # commit-ordering => watermark sets are closures: no domino below 0
        assert all(v >= 0 for v in t.values())

    def test_decision_invalidates(self):
        d = RollbackDecision(fsn=1, failed="A", targets={"A": 1, "B": 0})
        assert d.invalidates(Vertex("A", 0, 2))
        assert not d.invalidates(Vertex("A", 0, 1))
        assert not d.invalidates(Vertex("A", 1, 5))  # created post-recovery
        assert d.invalidates(Vertex("B", 0, 1))
        assert not d.invalidates(Vertex("C", 0, 9))  # not a participant


# --------------------------------------------------------------------------- #
# single StateObject basics                                                    #
# --------------------------------------------------------------------------- #
class TestSingleSO:
    def test_connect_persists_v0_and_actions_run(self, cluster_factory, tmp_path):
        c = cluster_factory(group_commit_interval=0.005)
        so = c.add("ctr", make_counter(tmp_path, "ctr"))
        assert so.runtime.stats()["committed"] == 0
        v, h = so.increment(None)
        assert v == 1 and h.deps
        (dep,) = h.deps
        assert dep.so_id == "ctr" and dep.world == 0

    def test_barrier_waits_for_durability(self, cluster_factory, tmp_path):
        c = cluster_factory(group_commit_interval=0.005)
        so = c.add("ctr", make_counter(tmp_path, "ctr"))
        assert so.StartAction(None)
        so.value += 10
        t = so.Detach()
        t.Barrier(timeout=5.0)
        # after the barrier our own vertex is inside the boundary
        st = so.runtime.stats()
        assert st["boundary"]["ctr"] >= 1
        assert so.Merge(t)
        so.EndAction()

    def test_restart_resumes_from_persisted_prefix(self, cluster_factory, tmp_path):
        c = cluster_factory(group_commit_interval=0.005)
        so = c.add("ctr", make_counter(tmp_path, "ctr"))
        assert so.StartAction(None)
        so.value = 42
        t = so.Detach()
        t.Barrier(timeout=5.0)
        assert so.Merge(t)
        so.EndAction()
        so2 = c.kill("ctr")
        assert so2 is not so
        assert so2.value == 42  # durable prefix survived the crash
        assert so2.runtime.world == 1


# --------------------------------------------------------------------------- #
# commit ordering (Def 4.1)                                                    #
# --------------------------------------------------------------------------- #
class TestCommitOrdering:
    def test_relabel_mode_bumps_receiver_version(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "p"))
        q = c.add("q", make_counter(tmp_path, "q"))
        for _ in range(4):
            p.runtime.maybe_persist(force=True)  # p's v_cur -> 5
        _, h = p.increment(None)
        assert h.max_version_for() == 5
        _, hq = q.increment(h)
        # receiver label >= sender label (no blocking in relabel mode)
        assert hq.max_version_for() >= 5
        assert q.runtime.stats()["v_cur"] >= 5

    def test_strict_mode_blocks_via_persistence(self, cluster_factory, tmp_path):
        c = cluster_factory(
            refresh_interval=None, group_commit_interval=99, strict_commit_ordering=True
        )
        p = c.add("p", make_counter(tmp_path, "sp"))
        q = c.add("q", make_counter(tmp_path, "sq"))
        for _ in range(4):
            p.runtime.maybe_persist(force=True)
        _, h = p.increment(None)
        before = len(q.runtime.stats()["labels"])
        _, hq = q.increment(h)
        after = len(q.runtime.stats()["labels"])
        # paper-literal behaviour: q persisted repeatedly to catch up
        assert after > before
        assert hq.max_version_for() >= 5


# --------------------------------------------------------------------------- #
# group commit (maybe_persist due/dirty/force semantics)                       #
# --------------------------------------------------------------------------- #
class TestGroupCommit:
    def test_dirty_but_not_due_skips(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=999)
        so = c.add("g", make_counter(tmp_path, "g"))
        so.increment(None)
        assert so.runtime.maybe_persist() is None  # dirty, interval not elapsed

    def test_due_but_clean_skips(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=0.0)
        so = c.add("g", make_counter(tmp_path, "g"))
        # v0 was persisted at Connect and nothing has dirtied state since:
        # an always-due interval alone must not trigger an empty persist.
        assert so.runtime.maybe_persist() is None

    def test_due_and_dirty_persists(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=0.0)
        so = c.add("g", make_counter(tmp_path, "g"))
        so.increment(None)
        label = so.runtime.maybe_persist()
        assert label is not None and label >= 1
        assert so.runtime.maybe_persist() is None  # clean again afterwards

    def test_force_persists_even_clean_and_not_due(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=999)
        so = c.add("g", make_counter(tmp_path, "g"))
        assert so.runtime.maybe_persist(force=True) is not None

    def test_persist_if_dirty_ignores_interval_not_cleanliness(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=999)
        so = c.add("g", make_counter(tmp_path, "g"))
        assert so.runtime.persist_if_dirty() is None  # v0 holds the state
        so.increment(None)
        assert so.runtime.persist_if_dirty() == 1
        assert so.runtime.persist_if_dirty() is None

    def test_queued_group_commits_persist_once(self, cluster_factory, tmp_path):
        # two group commits find the state dirty while an action holds the
        # epoch; the one that gets the exclusive epoch second finds the state
        # already taken and persists nothing
        c = cluster_factory(refresh_interval=None, group_commit_interval=0.0)
        so = c.add("g", make_counter(tmp_path, "g"))
        assert so.StartAction(None)
        labels = []
        commits = [
            threading.Thread(target=lambda: labels.append(so.runtime.maybe_persist()))
            for _ in range(2)
        ]
        for t in commits:
            t.start()
        time.sleep(0.1)  # both pass the dirty check and queue on the epoch
        so.EndAction()
        for t in commits:
            t.join(timeout=10)
        assert sorted(labels, key=str) == [1, None]


# --------------------------------------------------------------------------- #
# rollback + message discard                                                   #
# --------------------------------------------------------------------------- #
class TestRollback:
    def test_speculative_consumer_rolls_back(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "rp"))
        q = c.add("q", make_counter(tmp_path, "rq"))
        _, h = p.increment(None)          # speculative: never persisted
        res = q.increment(h, by=100)      # q consumed speculative state
        assert res is not None and q.value == 100
        c.kill("p")                        # p loses its in-memory increment
        c.refresh_all()                    # deliver the decision to q
        assert q.value == 0                # q rolled back to v0
        assert q.runtime.world == 1
        # stale header from the pre-failure epoch must be discarded
        assert q.increment(h) is None

    def test_skip_rollback_for_unaffected_peer(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "kp"))
        q = c.add("q", make_counter(tmp_path, "kq"))
        b = c.add("b", make_counter(tmp_path, "kb"))
        _, h = p.increment(None)
        q.increment(h, by=100)
        b.increment(None, by=7)           # b never saw p's speculative state
        c.kill("p")
        c.refresh_all()
        assert q.value == 0               # affected: rolled back
        assert b.value == 7               # §5.3 mitigation: skip, keep in-mem
        assert b.runtime.world == 1       # but the epoch still advances

    def test_durable_state_survives_peer_failure(self, cluster_factory, tmp_path):
        c = cluster_factory(group_commit_interval=0.005)
        p = c.add("p", make_counter(tmp_path, "dp"))
        q = c.add("q", make_counter(tmp_path, "dq"))
        _, h = p.increment(None)
        assert q.StartAction(h)
        q.value += 100
        t = q.Detach()
        t.Barrier(timeout=5.0)            # now both p@1 and q@1 are durable
        assert q.Merge(t)
        q.EndAction()
        c.kill("p")
        c.refresh_all()
        assert q.value == 100             # inside the boundary: survives

    def test_decision_targeting_unreported_v0_clamps_to_floor(
        self, cluster_factory, tmp_path
    ):
        """A decision computed before our synchronous v0 report arrived can
        assign target -1; the runtime must clamp to its durable floor (the
        Connect-time snapshot) instead of attempting Restore(-1)."""
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        z = c.add("z", make_counter(tmp_path, "z"))
        z.increment(None)
        z.runtime._apply_decision(
            RollbackDecision(fsn=1, failed="other", targets={"z": -1})
        )
        assert z.runtime.world == 1
        assert z.value == 0  # restored to v0, not v-1

    def test_rolled_back_sthread_raises(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "tp"))
        q = c.add("q", make_counter(tmp_path, "tq"))
        _, h = p.increment(None)
        assert q.StartAction(h)
        t = q.Detach()                    # sthread derives from speculative q
        c.kill("p")
        c.refresh_all()
        with pytest.raises(RolledBackError):
            t.Send()
        assert not q.Merge(t)


# --------------------------------------------------------------------------- #
# recovery partition rule (Def 4.3)                                            #
# --------------------------------------------------------------------------- #
class TestEpochPartition:
    def test_old_world_discarded_future_world_delayed(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "ep"))
        q = c.add("q", make_counter(tmp_path, "eq"))
        _, h_old = p.increment(None)      # world-0 header
        q2 = c.kill("q")                  # fsn=1; q2 is post-recovery
        # p has not yet heard of the failure: p stays in world 0
        assert p.runtime.world == 0
        # post-recovery q2 receives a pre-recovery message: m < x => discard
        assert q2.increment(h_old) is None
        # pre-recovery p receives a post-recovery message: m > x => delay
        _, h_new = q2.increment(None)
        with pytest.raises(DelayMessage):
            p.increment(h_new)
        p.Refresh()                       # applies the decision, world -> 1
        assert p.runtime.world == 1
        assert p.increment(h_new) is not None

    def test_recovery_sequencing_applies_decisions_in_order(
        self, cluster_factory, tmp_path
    ):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "qp"))
        a = c.add("a", make_counter(tmp_path, "qa"))
        b = c.add("b", make_counter(tmp_path, "qb"))
        c.kill("a")
        c.kill("b")
        assert p.runtime.world == 0
        p.Refresh()                       # both decisions arrive together
        assert p.runtime.world == 2       # applied 1 then 2 (Def 4.2)


# --------------------------------------------------------------------------- #
# coordinator failure + recovery (paper §4.3)                                  #
# --------------------------------------------------------------------------- #
class TestCoordinatorRecovery:
    def test_boundary_unavailable_until_fragments_resent(
        self, cluster_factory, tmp_path
    ):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "cp"))
        q = c.add("q", make_counter(tmp_path, "cq"))
        _, h = p.increment(None)
        q.increment(h)
        assert wait_committed(p, p.runtime.maybe_persist(force=True))
        assert wait_committed(q, q.runtime.maybe_persist(force=True))
        c.refresh_all()
        old_boundary = c.coordinator.current_boundary()
        assert old_boundary is not None

        c.restart_coordinator()
        # view incomplete: no boundary answers yet
        assert c.coordinator.current_boundary() is None
        assert c.coordinator.stats()["awaiting"] == ["p", "q"]
        c.refresh_all()                    # participants resend fragments
        new_boundary = c.coordinator.current_boundary()
        assert new_boundary is not None
        # view is at least as fresh as before the coordinator failure
        for so, wm in old_boundary.items():
            assert new_boundary[so] >= wm

    def test_failure_decisions_survive_coordinator_restart(
        self, cluster_factory, tmp_path
    ):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "fp"))
        q = c.add("q", make_counter(tmp_path, "fq"))
        _, h = p.increment(None)
        q.increment(h, by=100)
        c.kill("p")                        # decision fsn=1 durably logged
        c.restart_coordinator()
        c.refresh_all()                    # resend fragments; deliver decision
        c.refresh_all()
        assert q.value == 0                # rollback still applied
        assert q.runtime.world == 1

    def test_so_failure_during_coordinator_recovery_waits(
        self, cluster_factory, tmp_path
    ):
        c = cluster_factory(refresh_interval=0.002, group_commit_interval=0.005)
        p = c.add("p", make_counter(tmp_path, "wp"))
        q = c.add("q", make_counter(tmp_path, "wq"))
        p.increment(None)
        c.restart_coordinator()
        # kill + restart q while the coordinator is still collecting
        # fragments: connect must block until p has resent, then decide.
        q2 = c.kill("q")
        assert q2.runtime.world == 1


# --------------------------------------------------------------------------- #
# O(delta) hot path: seq-gated polls + compacted decision index (DESIGN §9)    #
# --------------------------------------------------------------------------- #
class TestPollDelta:
    def _coord(self, tmp_path):
        from repro.core import Coordinator

        return Coordinator(tmp_path / "coord.jsonl")

    def test_poll_gates_boundary_on_seq(self, tmp_path):
        from repro.core import PersistReport

        coord = self._coord(tmp_path)
        coord.connect("A", [])
        coord.report("A", [PersistReport(Vertex("A", 0, 1), ())])
        first = coord.poll("A", 0)
        assert first.boundary == {"A": 1}
        # nothing moved: quoting the seq back elides the boundary entirely
        again = coord.poll("A", 0, first.boundary_seq)
        assert again.boundary is None
        assert again.boundary_seq == first.boundary_seq
        # progress bumps the seq and ships the new boundary
        coord.report("A", [PersistReport(Vertex("A", 0, 2), ())])
        moved = coord.poll("A", 0, first.boundary_seq)
        assert moved.boundary == {"A": 2}
        assert moved.boundary_seq > first.boundary_seq
        coord.close()

    def test_poll_decisions_are_a_delta(self, cluster_factory, tmp_path):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "dp"))
        c.add("q", make_counter(tmp_path, "dq"))
        p.increment(None)
        c.kill("p")  # decision fsn=1
        c.kill("p")  # decision fsn=2
        assert [d.fsn for d in c.coordinator.poll("q", 0).decisions] == [1, 2]
        assert [d.fsn for d in c.coordinator.poll("q", 1).decisions] == [2]
        assert c.coordinator.poll("q", 2).decisions == []

    def test_decision_index_matches_linear_scan(self):
        from repro.core import DecisionIndex
        from repro.core.ids import vertex_rolled_back

        decisions = [
            RollbackDecision(fsn=1, failed="A", targets={"A": 1, "B": 0}),
            RollbackDecision(fsn=3, failed="B", targets={"B": 4, "C": 2}),
            RollbackDecision(fsn=5, failed="A", targets={"A": 7, "B": 2}),
        ]
        idx = DecisionIndex(decisions)
        for so in "ABCD":
            for world in range(7):
                for version in range(-1, 9):
                    v = Vertex(so, world, version)
                    assert idx.invalidates(v) == vertex_rolled_back(v, decisions), v

    def test_runtime_forgets_seq_on_coordinator_restart(
        self, cluster_factory, tmp_path
    ):
        c = cluster_factory(refresh_interval=None, group_commit_interval=99)
        p = c.add("p", make_counter(tmp_path, "sp"))
        p.increment(None)
        assert wait_committed(p, p.runtime.maybe_persist(force=True))
        c.refresh_all()
        assert p.runtime.boundary.get("p", -1) >= 1
        c.restart_coordinator()
        c.refresh_all()  # resend_fragments resets the known seq...
        c.refresh_all()  # ...so the next poll ships the full boundary again
        assert p.runtime.boundary.get("p", -1) >= 1
