"""Operations and bytes of the Pallas kernel ``rns_matmul``: C residue
channels of an (M, K) x (K, N) int8 product with int32 accumulation.

Per call: 2*C*M*K*N integer operations; C*(M*K + K*N) int8 bytes in and
4*C*M*N bytes out.  Counted for every residue matmul of the traced
interval, as the configuration's family lists them
(``matmul_calls``, ``harness.arch``).
"""
TRACE = "rns_matmul_pallas"      # the kernel's op name in the device trace
PEAK = "int8_ops"


def cost(run):
    """(operations, bytes) of the calls in the traced interval; None where
    the configuration has no residue matmuls."""
    if run.config["system"] != "rns":
        return None
    C = len(run.config["rns_moduli"])
    ops = byts = 0.0
    for M, K, N, reps in run.family.matmul_calls(run):
        ops += 2.0 * C * M * K * N * reps
        byts += float(C * (M * K + K * N) + 4 * C * M * N) * reps
    return ops, byts
