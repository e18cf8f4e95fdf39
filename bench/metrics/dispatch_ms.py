"""Median ``session.dispatch`` span, in ms: the host's call of the jitted
decode step for one token, which returns once the step is enqueued."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "serve")
    if recs is None:
        return None
    return ps.median(r.seconds for r in ps.need(ps.named(recs, "session.dispatch"), "session.dispatch")) * 1e3
