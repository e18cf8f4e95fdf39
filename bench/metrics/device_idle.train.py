"""Device idle share (%) over a few traced steps of a training cell that
makes no save."""
from bench.trace import idle_percent


def read(run):
    if not (run.get("driver") == "train" and not run.get("save_stalls")):
        return None
    return idle_percent(run)
