"""The decode step's share of its roofline, in %: the least time the chip
could take for the step (the larger of needed FLOPs over peak FLOP/s and
needed bytes over peak bytes/s; ``chipbench/flops.py``) over the device
time of ``jit_serve_step``, both averaged over the traced steps. Needed
bytes are the weights once and the K/V of the positions each sequence
attends, not the whole max_seq cache."""
from chipbench import flops as F

PROGRAM = "jit_serve_step"


def read(r):
    calls = r.trace.program_calls.get(PROGRAM, 0)
    indices = r.host.get("decode_indices") or []
    if not calls or not indices:
        return None
    if calls != len(indices):
        raise ValueError(f"the trace holds {calls} of the {len(indices)} "
                         f"{PROGRAM} calls the window made")
    batch = r.host["batch"]
    bound = sum(F.decode_step_bound_s(r.shape, [i + 1] * batch, r.peaks)
                for i in indices) / len(indices)
    return 100.0 * bound / (r.trace.program_s[PROGRAM] / calls)
