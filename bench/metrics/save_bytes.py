"""Median bytes of the trainer store's writes of the versions after the
connect-time one: what one save writes."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    writes = [r for r in ps.named(recs, "store.write", store=ps.TRAINER) if r.attrs["version"] > 0]
    return ps.median(r.attrs["bytes"] for r in ps.need(writes, "trainer store.write (version > 0)"))
