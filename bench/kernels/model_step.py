"""Model FLOPs of the work a window completed, the same for every number
system, as the configuration's family counts them (``prompt_flops`` and
``token_flops``, ``harness.arch``): each prompt once, and each generated
token with the positions its step attends over.  Padding and recomputation
are not counted."""
PEAK = "bf16_flops"


def useful_flops(run):
    fam = run.family
    flops = 0.0
    for n in run.prompt_lengths():
        flops += fam.prompt_flops(run, n)
    for pos0, remaining, steps in run.segments():
        for p, r in zip(pos0, remaining):
            for i in range(min(steps, r)):
                flops += fam.token_flops(run, p + i + 1)
    return flops
