"""Steps: mean device time of one execution of the paged decode program
(``jit_decode_step``) in the traced window."""
from bench import trace as T

PROGRAM = "jit_decode_step"


def read(ctx):
    chip = ctx["chip"]
    if chip is None:
        return None
    n, s = T.programs(chip).get(PROGRAM, (0, 0.0))
    return s / n * 1e3 if n else None
