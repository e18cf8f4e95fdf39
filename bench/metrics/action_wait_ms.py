"""Milliseconds the sessions' actions waited for the epoch, per decoded
token: the summed ``epoch.shared_wait`` spans (the slow path, taken while a
persist holds or waits for the exclusive epoch) over the number of
``session.dispatch`` spans."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "serve")
    if recs is None:
        return None
    tokens = len(ps.need(ps.named(recs, "session.dispatch"), "session.dispatch"))
    return ps.seconds(ps.named(recs, "epoch.shared_wait")) / tokens * 1e3
