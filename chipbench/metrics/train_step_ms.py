"""Mean device time of one training step program (``jit_train_step``) in
the traced window, in ms, averaged over the chips."""

PROGRAM = "jit_train_step"


def read(r):
    calls = r.trace.program_calls.get(PROGRAM, 0)
    if not calls:
        return None
    return r.trace.program_s[PROGRAM] / calls * 1e3
