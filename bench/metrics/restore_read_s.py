"""Seconds of the trainer store's ``store.read`` (the stored version read
from disk) inside the trainer's last ``restore``: the resume after the
kill."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    _, inside = ps.trainer_restore(recs)
    return ps.seconds(ps.need(ps.named(inside, "store.read", store=ps.TRAINER),
                              "store.read in the trainer's restore"))
