"""Bytes of matrix-sized operands the selector materialised beyond the
resident training matrix (the split, a gathered fold batch, standardized
copies: ``sweepOperandBytes``), per train, in GB."""


def read(run):
    if not run.units or "sweepOperandBytes" not in run.counters:
        return None
    return run.counters["sweepOperandBytes"] / run.units / 1e9
