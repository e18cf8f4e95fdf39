"""90th percentile (nearest rank) of the sessions' ``barrier`` spans, in
ms: the wait inside ``stream_durable`` until a turn's tokens are durable
(forced persist, report, poll). The in-program counterpart of
``release_lag_p90_ms``."""
from bench import program_spans as ps
from bench.harness import p_quantile


def read(run):
    recs = ps.records(run, "serve")
    if recs is None:
        return None
    return p_quantile([r.seconds for r in ps.need(ps.named(recs, "barrier"), "barrier")], 0.9) * 1e3
