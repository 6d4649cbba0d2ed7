"""Host loop: mean host time of one pass of the paged serving loop
outside its token pulls (each ``serve.iteration`` span less the
``serve.*.pull`` spans inside it; ``bench/spans.py``), over the passes
in the traced window: host work that the synchronous loop puts in
series with the device.  Read from the run's trace under
``harness.TRACE_DIR``; nothing where the program writes no spans."""
from bench import harness as H
from bench import spans as S


def read(ctx):
    n, ms = S.host_ms_per_iter(S.load(H.TRACE_DIR))
    return ms if n else None
