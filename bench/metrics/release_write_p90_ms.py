"""90th percentile (nearest rank) of the session stores' ``store.write``
spans, in ms: writing, syncing and renaming one version of a session's
tokens."""
from bench import program_spans as ps
from bench.harness import p_quantile


def read(run):
    recs = ps.records(run, "serve")
    if recs is None:
        return None
    writes = ps.need(ps.named(recs, "store.write"), "store.write")
    return p_quantile([r.seconds for r in writes], 0.9) * 1e3
