"""Model FLOPs utilisation of the train-step program, in %: the model
FLOPs of one step (``bench/work.py``: 6 per matmul weight per token, the
SSD terms, no recomputation) over the device time per execution of the
step program (``jit_train_step``) in the trace, over the chip's bf16 peak.
Idle time between steps is not in it; ``device_idle.train`` has that. A
trace that does not hold the program once per traced step is an error."""
from bench.peaks import peaks
from bench.trace import module_time
from bench.work import train_flops_per_step


def read(run):
    t = run.get("trace")
    if run.get("driver") != "train" or t is None or run.get("rehearsal"):
        return None
    n, seconds = module_time(t, "jit_train_step")
    if n != run["traced_steps"] or not n:
        raise RuntimeError(f"the trace holds {n} executions of 'jit_train_step'; the driver "
                           f"ran {run['traced_steps']} steps while traced")
    flops = train_flops_per_step(run["conf"], run["batch"], run["seq_len"])
    return 100.0 * flops / (seconds / n) / peaks(run["device_kind"])["bf16_flops_per_s"]
