"""Model operations of a decode step over its device time times the
chip's peak bf16 rate, in %: the whole decode step's share of the peak,
beside its roofline. Means over the traced ticks that decoded and over
the batched decode program's executions in the traced span."""
import flops


def read(run):
    if run.trace is None:
        return None
    steps = [t for t in run.traced_ticks if t.decode_kv_lens]
    dev = sum(v for k, v in run.trace.module_s.items() if "step_batched" in k)
    n = sum(v for k, v in run.trace.module_n.items() if "step_batched" in k)
    if not steps or not n or dev <= 0:
        return None
    ops = sum(flops.decode_flops(run.config, t.decode_kv_lens) for t in steps) / len(steps)
    return 100.0 * ops / (dev / n * run.peaks["bf16_flops_per_s"])
