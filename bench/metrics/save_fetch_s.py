"""Median, over the trainer's saves, of the seconds its ``trainer.fetch``
spans took: each leaf's copy from the device to the host."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    return ps.median(ps.seconds(ps.need(ps.named(ps.below(recs, s), "trainer.fetch"), "trainer.fetch"))
                     for s in ps.trainer_saves(recs))
