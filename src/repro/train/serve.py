"""Speculative serving loop: decode sessions as StateObjects.

The serving counterpart of train/loop.py. Session state (generated tokens +
cursor) is durable-by-DSE: the KV cache is *derived* state — on restore the
session replays its surviving token prefix through ``prefill`` to rebuild
the cache (cheap relative to the failure rate, exactly the paper's
trade). Responses stream to clients only behind speculation barriers.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import LocalCluster, StateObject, VersionStore, spans
from ..models import cache_descs, decode_step, forward
from ..models.config import ModelConfig
from ..models.params import is_desc


class DecodeSessionStateObject(StateObject):
    """Tokens + cursor are the durable truth; the KV cache is derived."""

    def __init__(self, root: Path, cfg: ModelConfig, params, max_len: int = 64,
                 extras: Optional[dict] = None) -> None:
        super().__init__()
        self.store = VersionStore(root)
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.extras = extras or {}
        self.tokens: List[int] = []
        self._cache = self._empty_cache()
        self._step = jax.jit(
            lambda p, c, t, i: decode_step(cfg, p, c, t, i, extras=self.extras)
        )

    def _empty_cache(self):
        return jax.tree_util.tree_map(
            lambda d: jnp.zeros(d.shape, jnp.float32),
            cache_descs(self.cfg, batch=1, max_len=self.max_len),
            is_leaf=is_desc,
        )

    def _rebuild_cache(self) -> None:
        """Replay surviving tokens to reconstruct the derived KV cache."""
        self._cache = self._empty_cache()
        tok = jnp.zeros((1, 1), jnp.int32)
        for i, t in enumerate([0] + self.tokens[:-1] if self.tokens else []):
            _, self._cache = self._step(
                self.params, self._cache,
                jnp.asarray([[t]], jnp.int32), jnp.asarray(i, jnp.int32),
            )

    # -- persistence -----------------------------------------------------
    def Persist(self, version: int, metadata: bytes, callback: Callable[[], None]) -> None:
        payload = np.asarray(self.tokens, np.int32).tobytes()

        def _io() -> None:
            try:
                self.store.write(version, payload, metadata)
            except RuntimeError:
                return
            callback()

        self.spawn_io(_io)

    def Restore(self, version: int) -> bytes:
        payload, meta = self.store.read(version)
        self.tokens = np.frombuffer(payload, np.int32).tolist()
        self._rebuild_cache()
        return meta

    def ListVersions(self):
        return self.store.list_versions()

    def Prune(self, version: int) -> None:
        self.store.prune(version)

    def on_crash(self) -> None:
        self.store.poison()
        self.store.drop_memory()
        self.tokens = []
        self._cache = self._empty_cache()

    # -- service API -------------------------------------------------------
    def generate(self, n: int) -> Optional[List[int]]:
        """Speculatively decode ``n`` tokens (one action per token)."""
        out = []
        so_id = self.runtime.so_id
        for _ in range(n):
            if not self.StartAction(None):
                return None
            idx = len(self.tokens)
            if idx >= self.max_len:
                self.EndAction()
                break
            prev = self.tokens[-1] if self.tokens else 0
            with spans.span("session.dispatch", so_id=so_id):
                logits, self._cache = self._step(
                    self.params, self._cache,
                    jnp.asarray([[prev]], jnp.int32), jnp.asarray(idx, jnp.int32),
                )
            with spans.span("session.sync", so_id=so_id):
                t = int(jnp.argmax(logits[0, 0, : self.cfg.vocab_size]))
            self.tokens.append(t)
            out.append(t)
            self.EndAction()
        return out

    def stream_durable(self, timeout: float = 30.0) -> Optional[List[int]]:
        """Barrier-gated export: only non-speculative tokens leave."""
        if not self.StartAction(None):
            return None
        if not self.wait_durable(timeout=timeout):
            return None
        out = list(self.tokens)
        self.EndAction()
        return out


@dataclass
class ServeRunResult:
    tokens_generated: int
    durable_tokens: List[int]
    rollbacks: int


def run_speculative_serving(
    root: Path,
    cfg: ModelConfig,
    params,
    *,
    n_tokens: int = 16,
    kill_at: Optional[int] = None,
    group_commit_interval: float = 0.02,
    extras: Optional[dict] = None,
) -> ServeRunResult:
    with LocalCluster(root, group_commit_interval=group_commit_interval) as cluster:
        mk = lambda: DecodeSessionStateObject(
            Path(root) / "sess", cfg, params, max_len=max(64, n_tokens + 1),
            extras=extras,
        )
        sess = cluster.add("session", mk)
        rollbacks = 0
        produced = 0
        while produced < n_tokens:
            sess = cluster.get("session")
            before = len(sess.tokens)
            out = sess.generate(min(4, n_tokens - produced))
            if out is None:
                cluster.refresh_all()
                continue
            produced = len(sess.tokens)
            if kill_at is not None and produced >= kill_at:
                cluster.kill("session")
                kill_at = None
                rollbacks += 1
                produced = len(cluster.get("session").tokens)
        durable = cluster.get("session").stream_durable() or []
        return ServeRunResult(
            tokens_generated=produced,
            durable_tokens=durable,
            rollbacks=rollbacks,
        )
