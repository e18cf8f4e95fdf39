"""Device idle share (%) over a few traced seconds of a serving cell's
clients."""
from bench.trace import idle_percent


def read(run):
    if not (run.get("driver") == "serve"):
        return None
    return idle_percent(run)
