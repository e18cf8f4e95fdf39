"""Median seconds a save held the training loop in the window: the host
clock around the trainer's ``persist_if_dirty()``, which returns once the
snapshot taken under the exclusive epoch (a synchronous copy of the whole
state to the host, then serialisation) is done. Cells without saves have
nothing to read."""
import statistics


def read(run):
    stalls = run.get("save_stalls")
    return statistics.median(stalls) if stalls else None
