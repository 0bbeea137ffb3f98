"""Mean device time of one decode step: executions of the batched decode
program (``jit__step_batched_fused``) in the traced span."""


def read(run):
    if run.trace is None:
        return None
    s = sum(v for k, v in run.trace.module_s.items() if "step_batched" in k)
    n = sum(v for k, v in run.trace.module_n.items() if "step_batched" in k)
    return s * 1e3 / n if n else None
