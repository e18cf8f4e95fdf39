"""Share of the HBM roofline reached by the hybrid's decode step, in %: the
bytes the traced decode steps had to move (``bench/work_hybrid.py``: every
weight once per application, the tied table read whole by the head, the
Mamba states read and written, the KV caches up to each step's own
position, the logits) over the chip's HBM bandwidth, over the decode
program's device time in the trace. A step's FLOPs take a few percent of
the bytes' time at the bf16 peak, so HBM bandwidth bounds it.

The yardstick is deliberate: the program reads all of a session's
``max_len`` cache slots today (``models/layers.py::attention`` masks the
empty ones and does not slice them away), so a change that reads only the
filled prefix raises this share.

Each traced decode's position is recovered from the readings: per session,
the tokens past the end of its last turn in the timed window were decoded
while traced. The decode program is found by the module name of the
session's own jitted step; a trace that does not hold it once per traced
decode step, or positions that do not add up to the traced decodes, is an
error, never a silent gap."""
from bench.peaks import peaks
from bench.trace import module_time
from bench.work_hybrid import decode_bytes


def traced_positions(run):
    """Positions (0-based) of the decode steps run while traced."""
    ends = {}
    for t in run["window_turns"]:
        ends[t["sid"]] = max(ends.get(t["sid"], 0), t["start"] + len(t["tokens"] or ()))
    return [p for sid, toks in run["sessions"].items() for p in range(ends.get(sid, 0), len(toks))]


def read(run):
    t = run.get("trace")
    if run.get("driver") != "serve" or t is None or run.get("rehearsal"):
        return None
    name, want = run["decode_module"], run["traced_decodes"]
    n, seconds = module_time(t, name)
    if n != want or not n:
        raise RuntimeError(f"the trace holds {n} executions of {name!r}; the clients ran "
                           f"{want} decode steps while traced")
    positions = traced_positions(run)
    if len(positions) != n:
        raise RuntimeError(f"{len(positions)} decode positions past the window's turns; the "
                           f"clients ran {n} decode steps while traced")
    moved = sum(decode_bytes(run["conf"], p) for p in positions)
    return 100.0 * moved / peaks(run["device_kind"])["hbm_bytes_per_s"] / seconds
