"""The paper's AI tax, in %: the share of the window's request time spent
outside the AI, ``100 * (pre + post + transfer + queue) / all``, with the
profiler off.

Every EventLog span of the window that ended before the traced span
began is put in a five-way bucket by the program's own categorisation
(``EventLog.five_way_seconds``: a span tagged ``kind="transfer"`` is
transfer, otherwise ``categorize`` of its stage). Batched spans count
once per request, each a slice of the batch's span, so a tick weighs by
the requests it served. A program whose spans carry no ``tick`` (no host
phase spans) reads nothing.
"""


def read(run):
    from repro.core.events import EventLog, categorize

    end = min((t.t0 for t in run.traced_ticks), default=run.window_s)
    spans = [(stage, s, e, meta) for stage, s, e, meta in run.events
             if 0 <= e < end]
    if not any("tick" in meta for *_, meta in spans):
        return None
    log = EventLog()
    for stage, s, e, meta in spans:
        log.log(0, stage, s, e, **meta)
    b = log.five_way_seconds(categorize)
    total = sum(b.values())
    return 100.0 * (total - b["ai"]) / total if total > 0 else None
