"""Device time of the prefill program per 1,000 prompt tokens, over the
traced span: executions of the program whose name holds ``prefill``
(``jit__prefill``), and the prompt lengths of the ticks traced."""


def read(run):
    if run.trace is None:
        return None
    s = sum(v for k, v in run.trace.module_s.items() if "prefill" in k)
    toks = sum(sum(t.prefill_lens) for t in run.traced_ticks)
    return s * 1e3 / (toks / 1e3) if s > 0 and toks else None
