"""Production mesh construction (TPU v5e target).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first init,
and only the dry-run is allowed to install the 512-placeholder-device flag.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod: 2 pods = 512
    chips with a pure-DP "pod" axis (cross-pod traffic = grad all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline (per chip).
TPU_V5E = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_link_bw": 50e9,         # B/s per link (approximate)
    "hbm_bytes": 16 * 2**30,
}
