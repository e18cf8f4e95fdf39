"""Trainer / metrics StateObjects — the paper's StateObject abstraction
instantiated over JAX training state (DESIGN.md §2 mapping).

TrainerStateObject:
  * one ``train_on`` call = one libDSE action: it consumes the data
    pipeline's header (the batch-lineage edge) and emits a header for
    downstream consumers (metrics/eval/export);
  * ``Persist`` captures a consistent snapshot under the runtime's
    exclusive epoch (no step interleaves): every leaf copied from the
    device to the host, which is what lets the next train step donate its
    inputs. That host copy is the snapshot. Encoding it into the archive
    and writing it happen behind the loop, on persist IO threads
    ("write-behind": one fills the archive, the other writes each part as
    soon as it is final), while steps keep executing SPECULATIVELY past the
    checkpoint: the paper's persistence-off-critical-path. At most one
    write-behind is in flight per trainer; a save that finds the previous
    one running waits for it before its own copy. With the delta codec the
    encode reads the device state and advances the chain under the epoch,
    so only the write is behind;
  * ``Restore`` loads params/opt/step; with the DeltaCheckpointCodec,
    versions between bases are int8 deltas (Pallas delta_encode kernel).

MetricsStateObject:
  * records (step, loss) under actions that consume trainer headers, so a
    rolled-back step's metric is rolled back with it;
  * ``flush_external`` is barrier-gated — the outside world only ever sees
    metrics that survive any failure (Failure Transparency).
"""
from __future__ import annotations

import hashlib
import io
import json
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..core import spans
from ..core.clock import REAL_CLOCK
from ..core.ids import Header
from ..core.state_object import StateObject, VersionStore
from . import archive
from .delta import DeltaCheckpointCodec, _flatten


def params_digest(params) -> str:
    """Bit-exact fingerprint of a parameter tree (float32 stream)."""
    flat, _, _ = _flatten(params)
    return hashlib.sha256(np.ascontiguousarray(flat)).hexdigest()[:16]


class _Fill:
    """An archive filled on one thread while another writes it out: the
    writer's ``ready(n)`` waits until the first ``n`` bytes are final, and
    is False once the fill has stopped short of them."""

    def __init__(self, clock) -> None:
        self._cv = clock.condition()
        self._filled = 0
        self._over = False
        #: set once the fill has ended, whole or not
        self.done = clock.event()

    def run(self, fill: Callable, stopped: Callable[[], bool], parent: int) -> None:
        try:
            fill(stopped, self._advance, parent)
        finally:
            with self._cv:
                self._over = True
                self._cv.notify_all()
            self.done.set()

    def _advance(self, n: int) -> None:
        with self._cv:
            self._filled = n
            self._cv.notify_all()

    def ready(self, n: int) -> bool:
        with self._cv:
            while self._filled < n and not self._over:
                self._cv.wait()
            return self._filled >= n


class TrainerStateObject(StateObject):
    def __init__(
        self,
        root: Path,
        init_state_fn: Callable[[], Tuple],   # () -> (params, opt_state)
        step_fn: Callable,                    # (params, opt, batch) -> (params, opt, loss)
        codec: Optional[DeltaCheckpointCodec] = None,
        save_log: Optional[List[Tuple[int, float, float]]] = None,
    ) -> None:
        super().__init__()
        # one version is the whole training state (5 GB at mamba2-370m in
        # fp32 with Adam), so the memory tier keeps only the newest
        self.store = VersionStore(root, keep_in_memory=1)
        with spans.span("trainer.init"):
            self.params, self.opt_state = init_state_fn()
            if spans.enabled():
                # the span ends with the state made; unrecorded, the first
                # use of the state waits for it instead
                jax.block_until_ready((self.params, self.opt_state))
        self.step_fn = step_fn
        self.step = 0
        # loss history is part of trainer state: it rolls back and replays
        # atomically with params/step (exactly-once metrics reconciliation)
        self.loss_history: List[Tuple[int, float]] = []
        self.codec = codec
        self._prev_flat: Optional[np.ndarray] = None
        self._last_label: Optional[int] = None
        self._since_base = 0
        self._chain: Dict[int, bytes] = {}   # version -> blob (delta mode)
        #: set once the newest save's write-behind has ended (None: no save yet)
        self._written = None
        #: set by ``on_crash``: a write-behind in progress stops at its next leaf
        self._crashed = False
        self.bytes_written = 0
        #: (step, snapshot seconds, seconds until durable) per completed
        #: save; shared across incarnations when the caller passes a list
        self.save_log = save_log if save_log is not None else []

    # -- persistence ---------------------------------------------------------
    def _header(self, prev_label: Optional[int], is_base: bool) -> bytes:
        hdr = json.dumps({
            "step": self.step, "history": self.loss_history,
            "prev": prev_label, "base": is_base,
        }).encode()
        return len(hdr).to_bytes(4, "little") + hdr

    def _delta_blob(self, version: int) -> bytes:
        # chain bookkeeping: a delta's parent is the LAST PERSISTED label
        # of this incarnation's lineage. Walking explicit parent pointers
        # at restore time is immune to stale blobs from rolled-back
        # incarnations that share label ranges (DESIGN.md §2 gaps).
        force_base = (
            self._prev_flat is None
            or self._since_base >= self.codec.base_every
        )
        body, self._prev_flat = self.codec.encode(
            version, (self.params, self.opt_state), None if force_base else self._prev_flat
        )
        prev_label = None if force_base else self._last_label
        self._since_base = 0 if force_base else self._since_base + 1
        self._last_label = version
        return self._header(prev_label, force_base) + body

    @staticmethod
    def _split_blob(blob: bytes):
        n = int.from_bytes(blob[:4], "little")
        hdr = json.loads(blob[4 : 4 + n].decode())
        return hdr, blob[4 + n :]

    def Persist(self, version: int, metadata: bytes, callback: Callable[[], None]) -> None:
        # The runtime holds the exclusive epoch: no train step is in flight,
        # and none starts before this returns.
        t0 = time.perf_counter()
        step = self.step
        if self.codec is not None:
            blob = self._delta_blob(version)
            self._chain[version] = blob
            snapshot_s = time.perf_counter() - t0

            def _io() -> None:
                try:
                    self.store.write(version, blob, metadata)
                except RuntimeError:
                    return
                self._saved(step, snapshot_s, t0, len(blob), callback)

            self.spawn_io(_io)
            return
        if self._written is not None and not self._written.is_set():
            with spans.span("trainer.save_wait", version=version):
                self._written.wait()
        # A leaf at a time, each copy ended before the next is asked for:
        # on a v5e host 5.06 GB come over in 1.6-1.9 s this way, 2.4-2.8 s
        # with every copy issued first. All have ended when this returns:
        # the next step donates them.
        with spans.span("trainer.fetch", version=version):
            leaves = [np.asarray(x) for x in
                      jax.tree_util.tree_leaves((self.params, self.opt_state))]
        prefix = self._header(None, True)
        snapshot_s = time.perf_counter() - t0
        clock = self._runtime.clock if self._runtime is not None else REAL_CLOCK
        written = self._written = clock.event()
        handed_off = spans.current()

        def _write_behind() -> None:
            # the archive is filled on a second IO thread while this one
            # writes each part out as soon as it is final
            try:
                with spans.span("trainer.write_behind", parent=handed_off, version=version):
                    arc, fill = archive.Archive(prefix, leaves), _Fill(clock)
                    here = spans.current()
                    self.spawn_io(lambda: fill.run(arc.fill, lambda: self._crashed, here))
                    try:
                        self.store.write(version, arc.blob, metadata, ready=fill.ready)
                    except RuntimeError:
                        return
                    finally:
                        fill.done.wait()
                self._saved(step, snapshot_s, t0, len(arc.blob), callback)
            finally:
                written.set()

        self.spawn_io(_write_behind)

    def _saved(self, step: int, snapshot_s: float, t0: float, nbytes: int,
               callback: Callable[[], None]) -> None:
        """A version is durable: count it, log it, report it."""
        self.bytes_written += nbytes
        self.save_log.append((step, snapshot_s, time.perf_counter() - t0))
        callback()

    def Restore(self, version: int) -> bytes:
        payload, meta = self.store.read(version)
        with spans.span("trainer.decode"):
            hdr, body = self._split_blob(payload)
            del payload  # body is a copy: hold one, not two, of a 5 GB state
            if self.codec is not None:
                # walk explicit parent pointers down to a base (stale blobs
                # from rolled-back label ranges are never visited)
                bodies: List[bytes] = []
                v = version
                while True:
                    blob = self._chain.get(v)
                    if blob is None:
                        blob, _ = self.store.read(v)
                    h, b = self._split_blob(blob)
                    bodies.append(b)
                    if h.get("base", True) or h.get("prev") is None:
                        break
                    v = int(h["prev"])
                bodies.reverse()
                _, p_shapes, p_treedef = _flatten(self.params)
                _, o_shapes, o_treedef = _flatten(self.opt_state)
                state, flat = self.codec.decode_chain(
                    bodies, p_shapes, p_treedef, o_shapes, o_treedef
                )
                self._prev_flat = flat
                self._last_label = version
                self._since_base = 0  # force a fresh base on the next persist
            else:
                z = np.load(io.BytesIO(body))
                leaves, treedef = jax.tree_util.tree_flatten(
                    (self.params, self.opt_state)
                )
                state = jax.tree_util.tree_unflatten(treedef, [z[k] for k in z.files])
        with spans.span("trainer.upload"):
            self.params = self.opt_state = None  # free the replaced device state first
            self.params, self.opt_state = jax.device_put(state)
            if spans.enabled():
                jax.block_until_ready((self.params, self.opt_state))
        self.step = int(hdr["step"])
        self.loss_history = [tuple(r) for r in hdr["history"]]
        return meta

    def ListVersions(self) -> List[Tuple[int, bytes]]:
        return self.store.list_versions()

    def Prune(self, version: int) -> None:
        # keep delta-chain bases: prune only below the last base <= version
        if self.codec is not None:
            return  # simple policy: delta mode retains history (bounded runs)
        self.store.prune(version)

    def on_crash(self) -> None:
        self._crashed = True
        self.store.poison()
        self.store.drop_memory()
        self._chain = {}
        self._prev_flat = None
        self._last_label = None
        self._since_base = 0
        # the dead incarnation's device state is released, not re-created:
        # its replacement allocates its own
        self.params = self.opt_state = None
        self.step = 0
        self.loss_history = []

    # -- service API -----------------------------------------------------------
    def train_on(self, step: int, tokens: np.ndarray, header: Optional[Header] = None,
                 extras: Optional[dict] = None):
        """One speculative train step. Returns (loss, header) or None."""
        if not self.StartAction(header):
            return None
        if step != self.step:
            # stale/duplicate batch relative to restored state: refuse inside
            # the action so the driver resyncs the cursor.
            self.EndAction()
            return ("resync", self.step)
        batch = {"tokens": tokens, **(extras or {})}
        self.params, self.opt_state, loss = self.step_fn(
            self.params, self.opt_state, batch
        )
        loss = float(loss)
        self.loss_history.append((self.step, loss))
        self.step += 1
        return loss, self.EndAction()

    def current_step(self) -> int:
        return self.step

    def history_snapshot(self):
        """(history, header) under an action — for metrics reconciliation
        after a rollback dropped records the trainer state still covers."""
        if not self.StartAction(None):
            return None
        out = list(self.loss_history)
        return out, self.EndAction()

    def params_digest(self) -> str:
        return params_digest(self.params)


class MetricsStateObject(StateObject):
    def __init__(self, root: Path) -> None:
        super().__init__()
        self.store = VersionStore(root)
        self.records: List[Tuple[int, float]] = []
        self._mu = threading.Lock()

    def Persist(self, version: int, metadata: bytes, callback: Callable[[], None]) -> None:
        with self._mu:
            payload = json.dumps(self.records).encode()

        def _io() -> None:
            try:
                self.store.write(version, payload, metadata)
            except RuntimeError:
                return
            callback()

        self.spawn_io(_io)

    def Restore(self, version: int) -> bytes:
        payload, meta = self.store.read(version)
        with self._mu:
            self.records = [tuple(r) for r in json.loads(payload.decode())]
        return meta

    def ListVersions(self) -> List[Tuple[int, bytes]]:
        return self.store.list_versions()

    def Prune(self, version: int) -> None:
        self.store.prune(version)

    def on_crash(self) -> None:
        self.store.poison()
        self.store.drop_memory()
        with self._mu:
            self.records = []

    def record(self, step: int, loss: float, header: Optional[Header] = None) -> bool:
        if not self.StartAction(header):
            return False
        with self._mu:
            self.records.append((step, loss))
        self.EndAction()
        return True

    def flush_external(self, timeout: float = 30.0) -> List[Tuple[int, float]]:
        """Barrier-gated export: returns only non-speculative metrics."""
        if not self.StartAction(None):
            return []
        t = self.Detach()
        t.Barrier(timeout=timeout)
        if not self.Merge(t):
            return []
        with self._mu:
            out = list(self.records)
        self.EndAction()
        return out
