"""90th percentile, over the window's turns, of the host time from
``generate`` returning to ``stream_durable`` returning: what the barrier
adds to a turn (group commit, write, report, boundary)."""
from bench.harness import p_quantile


def read(run):
    lags = run.get("release_lags")
    return p_quantile(lags, 0.9) * 1e3 if lags else None
