"""Median seconds of the trainer store's ``store.write`` (write, fsync,
rename) of the versions after the connect-time one: the save's IO, off the
training loop's path."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    writes = [r for r in ps.named(recs, "store.write", store=ps.TRAINER) if r.attrs["version"] > 0]
    return ps.median(r.seconds for r in ps.need(writes, "trainer store.write (version > 0)"))
