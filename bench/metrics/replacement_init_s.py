"""Seconds the replacement trainer's ``trainer.init`` took: the seeded
state the incarnation that restored built before its ``restore`` replaced
it."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    restore, _ = ps.trainer_restore(recs)
    inits = [r for r in ps.named(recs, "trainer.init") if r.end_ns <= restore.start_ns]
    return ps.need(inits, "trainer.init before the trainer's restore")[-1].seconds
