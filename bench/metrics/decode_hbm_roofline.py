"""Share of the HBM roofline reached by the decode step, in %: the bytes a
decode step has to move (``bench/work.py``: weights, one embedding row,
conv windows and SSM states read and written, logits) over the chip's HBM
bandwidth, over the device time per execution of the decode program in the
trace. HBM bandwidth bounds it: a step's FLOPs take under 1% of the bytes'
time at the bf16 peak. The decode program is found by the module name of
the session's own jitted step; a trace that does not hold it once per
traced decode step is an error, never a silent gap."""
from bench.peaks import peaks
from bench.trace import module_time
from bench.work import decode_bytes


def read(run):
    t = run.get("trace")
    if run.get("driver") != "serve" or t is None or run.get("rehearsal"):
        return None
    name, want = run["decode_module"], run["traced_decodes"]
    n, seconds = module_time(t, name)
    if n != want or not n:
        raise RuntimeError(f"the trace holds {n} executions of {name!r}; the clients ran "
                           f"{want} decode steps while traced")
    return 100.0 * decode_bytes(run["conf"]) / peaks(run["device_kind"])["hbm_bytes_per_s"] / (seconds / n)
