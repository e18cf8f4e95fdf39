"""Median, over the trainer's saves, of the seconds its ``trainer.encode``
spans (each leaf's write into the archive) and its ``trainer.join`` (the
blob's header and archive joined into one buffer) took."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    out = []
    for s in ps.trainer_saves(recs):
        inside = ps.below(recs, s)
        out.append(ps.seconds(ps.need(ps.named(inside, "trainer.encode"), "trainer.encode"))
                   + ps.seconds(ps.need(ps.named(inside, "trainer.join"), "trainer.join")))
    return ps.median(out)
