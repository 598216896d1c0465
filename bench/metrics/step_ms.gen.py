"""Wall time inside ``paged_segment`` per decode step."""


def read(run):
    spans = run.spans("segment")
    steps = sum(s.attrs["steps"] for s in spans)
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / steps
