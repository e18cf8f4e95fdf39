"""Seconds of ``trainer.upload`` (the old state freed, the restored one put
on the device, until it is there) inside the trainer's last ``restore``:
the resume after the kill."""
from bench import program_spans as ps


def read(run):
    recs = ps.records(run, "ckpt")
    if recs is None:
        return None
    _, inside = ps.trainer_restore(recs)
    return ps.seconds(ps.need(ps.named(inside, "trainer.upload"),
                              "trainer.upload in the trainer's restore"))
