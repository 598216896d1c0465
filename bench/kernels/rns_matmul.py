"""Operations and bytes of the Pallas kernel ``rns_matmul``: C residue
channels of an (M, K) x (K, N) int8 product with int32 accumulation.

Per call: 2*C*M*K*N integer operations; C*(M*K + K*N) int8 bytes in and
4*C*M*N bytes out.  Counted for every residue matmul of a step: the seven
projections of each layer on every row the step is given (the batch for a
decode step, batch x bucket for a prefill) and the logits matmul on one
row per slot.
"""
TRACE = "rns_matmul_pallas"      # the kernel's op name in the device trace
PEAK = "int8_ops"


def matmuls(d):
    """``(K, N, per_layer)`` of each weight matmul of a step: the seven
    projections of a layer, then the tied logits matmul."""
    return [(d.d_model, d.q_width, True), (d.d_model, d.kv_width, True),
            (d.d_model, d.kv_width, True), (d.q_width, d.d_model, True),
            (d.d_model, d.d_ff, True), (d.d_model, d.d_ff, True),
            (d.d_ff, d.d_model, True), (d.d_model, d.vocab, False)]


def cost(run):
    """(operations, bytes) of the calls in the traced interval; None where
    the configuration has no residue matmuls."""
    if run.config["system"] != "rns":
        return None
    C = len(run.config["rns_moduli"])
    ops = byts = 0.0
    for rows, logit_rows, count in run.steps():
        for K, N, per_layer in matmuls(run.dims):
            M, reps = (rows, run.dims.layers) if per_layer else (logit_rows, 1)
            ops += 2.0 * C * M * K * N * reps * count
            byts += float(C * (M * K + K * N) + 4 * C * M * N) * reps * count
    return ops, byts
