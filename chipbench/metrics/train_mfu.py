"""Model FLOP utilization of the training step, in %: the step's model
FLOPs (``chipbench/train_flops.py``; recomputation not counted) over the
chips' bf16 peak times the mean device time of ``jit_train_step`` in the
traced window."""
from chipbench import train_flops as TF

PROGRAM = "jit_train_step"


def read(r):
    calls = r.trace.program_calls.get(PROGRAM, 0)
    if not calls or "seq" not in r.host:
        return None
    step_s = r.trace.program_s[PROGRAM] / calls
    flops = TF.train_step_flops(r.shape, r.host["batch"], r.host["seq"])
    return 100.0 * flops / (r.chips * r.peaks["bf16_flops"] * step_s)
