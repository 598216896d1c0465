"""Model FLOPs of the work a window completed, from the configuration's
shapes alone, the same for every number system: per token 2 x the weights
that take part in a matmul, plus 4*H*hd*context for its attention; the
logits matmul once per prompt (its last position) and once per generated
token.  Padding and recomputation are not counted."""
from harness.weights import matmul_params

PEAK = "bf16_flops"


def useful_flops(run):
    d = run.dims
    layer_w = matmul_params(d) - d.vocab * d.d_model
    att = 4.0 * d.layers * d.heads * d.head_dim
    flops = 0.0
    for n in run.prompt_lengths():
        flops += 2.0 * layer_w * n + att * n * (n + 1) / 2
        flops += 2.0 * d.vocab * d.d_model
    for pos0, remaining, steps in run.segments():
        for p, r in zip(pos0, remaining):
            k = min(steps, r)
            flops += (2.0 * matmul_params(d)) * k
            flops += att * (k * (p + 1) + k * (k - 1) / 2)
    return flops
