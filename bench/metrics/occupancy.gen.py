"""Scheduler batch occupancy: live slots x steps over batch x steps, summed
over the fused segments (a slot is live until its budget is spent)."""


def read(run):
    live = total = 0
    for pos0, remaining, steps in run.segments():
        live += sum(min(steps, r) for r in remaining)
        total += run.batch * steps
    return 100.0 * live / total if total else None
