"""Kernels: the decode attention kernel's share of its roofline inside
the paged decode program.  Least time per call (bytes of the live KV
blocks every slot reads, from the server's ``kv_read_frac``, and the
operations over its live rows, from ``kv_fill_frac``; see
``bench/flops.py``) over the mean device time per call in the trace."""
from bench import flops as F
from bench import trace as T

PROGRAM = "jit_decode_step"
KERNEL = r"^%flash_decode\.\d+ = "


def read(ctx):
    chip, st = ctx["chip"], ctx["stats"]
    if chip is None or "kv_read_frac" not in st:
        return None
    calls, secs = T.kernel(chip, KERNEL, PROGRAM)
    if not calls:
        return None
    cfg = ctx["config"]
    slots = cfg["server"]["slots"]
    rows = slots * ctx["capacity"]
    ops, nbytes = F.decode_attention_call(
        cfg["model"], cfg["precision"], slots,
        st["kv_read_frac"] * rows, st["kv_fill_frac"] * rows)
    return 100.0 * F.least_seconds(ops, nbytes, ctx["peaks"]) / (secs / calls)
