"""Seconds of ``trainer.decode`` (the blob split, ``np.load`` and the tree
rebuilt) inside the trainer's last ``restore``: the resume after the
kill."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    _, inside = ps.trainer_restore(recs)
    return ps.seconds(ps.need(ps.named(inside, "trainer.decode"),
                              "trainer.decode in the trainer's restore"))
