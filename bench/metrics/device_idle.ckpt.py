"""Device idle share (%) over one traced whole cycle of a training cell
that saves: ten steps and the save's stall."""
from bench.trace import idle_percent


def read(run):
    if not (run.get("driver") == "train" and run.get("save_stalls")):
        return None
    return idle_percent(run)
