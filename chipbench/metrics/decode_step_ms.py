"""Mean device time of one decode step program (``jit_serve_step``) in the
traced window, in ms."""

PROGRAM = "jit_serve_step"


def read(r):
    calls = r.trace.program_calls.get(PROGRAM, 0)
    if not calls:
        return None
    return r.trace.program_s[PROGRAM] / calls * 1e3
