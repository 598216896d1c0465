"""Operations and bytes of the Pallas kernel ``flash_paged_decode``: one
decode step of one layer over the page pool.

Per live slot of length n (its position + 1): the family's operations per
cached position times n (``paged_decode``, ``harness.arch``; 4*H*hd for a
dense block: QK and PV), and the pages its length covers,
ceil(n / page_size), at the pool's own bytes per page of one layer's rows
(codes, witness lanes and scales included), plus its query and output
rows.
"""
TRACE = "flash_paged_decode_pallas"      # the kernel's op name in the device trace
PEAK = "bf16_flops"


def cost(run):
    per_position, row_bytes, layers = run.family.paged_decode(run)
    ps = run.page_size
    ops = byts = 0.0
    for pos0, remaining, steps in run.segments():
        for p, r in zip(pos0, remaining):
            for i in range(min(steps, r)):
                n = p + i + 1
                ops += per_position * n
                byts += -(-n // ps) * run.page_bytes + row_bytes
    return ops * layers, byts * layers
