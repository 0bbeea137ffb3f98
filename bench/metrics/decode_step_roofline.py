"""Least time of a decode step over its device time, in %, over the
traced span: the mean least time of the traced ticks that decoded, over
the mean device time of the batched decode program's executions.

A step's least time is the larger of its operations over the chip's
peak bf16 rate and its bytes over the HBM bandwidth (``bench/flops.py``:
each weight once, the experts its tokens route to, the valid KV rows of
each active slot). At these batch sizes the bytes bound it."""
import flops


def read(run):
    if run.trace is None:
        return None
    steps = [t for t in run.traced_ticks if t.decode_kv_lens]
    dev = sum(v for k, v in run.trace.module_s.items() if "step_batched" in k)
    n = sum(v for k, v in run.trace.module_n.items() if "step_batched" in k)
    if not steps or not n or dev <= 0:
        return None
    c, p = run.config, run.peaks
    least = sum(max(flops.decode_flops(c, t.decode_kv_lens) / p["bf16_flops_per_s"],
                    flops.decode_bytes(c, t.decode_kv_lens) / p["hbm_bytes_per_s"])
                for t in steps) / len(steps)
    return 100.0 * least / (dev / n)
