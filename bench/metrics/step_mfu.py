"""Model: the whole step's share of the chip's peak over the traced
window.  Model operations of every token the traced window processed
(decode executions times the mean active slots, each attending over the
mean live context; chunk executions times the mean real prompt rows per
chunk, each over the mean prompt position), each precision over its own
peak, divided by the window.  Operations per token are the reference
module's (``bench/models/<reference>.py``)."""
from bench import flops as F
from bench import trace as T
from bench.models import reference


def read(ctx):
    chip = ctx["chip"]
    if chip is None or ctx["window_s"] <= 0:
        return None
    cfg, st = ctx["config"], ctx["stats"]
    m, prec = cfg["model"], cfg["precision"]
    token_flops = reference(cfg).token_flops
    slots = cfg["server"]["slots"]
    progs = T.programs(chip)
    n_dec = progs.get("jit_decode_step", (0, 0.0))[0]
    n_chk = progs.get("jit_paged_chunk_step", (0, 0.0))[0]
    if not (n_dec or n_chk) or not st.get("decode_steps"):
        return None
    active = st["slot_utilization"] * slots
    live = st["kv_fill_frac"] * slots * ctx["capacity"]
    dec = token_flops(m, prec, live / max(active, 1e-9))
    rows = ctx["prompt_tokens"] / max(st["prefill_chunks"], 1)
    pre = token_flops(m, prec, ctx["prompt_positions"]
                      / max(ctx["prompt_tokens"], 1))
    total = {k: n_dec * active * dec[k] + n_chk * rows * pre[k]
             for k in dec}
    return 100.0 * F.least_seconds(total, 0.0, ctx["peaks"]) / ctx["window_s"]
