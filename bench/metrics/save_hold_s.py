"""Median seconds a save of the trainer held the training loop, from the
program's own ``persist`` span (versions after the connect-time one): from
the call until the exclusive epoch is released. The in-program counterpart
of ``save_stall_s``."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    return ps.median(r.seconds for r in ps.trainer_saves(recs))
