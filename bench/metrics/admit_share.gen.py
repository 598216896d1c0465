"""Share of the window's wall time spent inside ``admit_prefill``."""


def read(run):
    spans = run.spans("admit")
    if not spans:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans) / run.window_s
