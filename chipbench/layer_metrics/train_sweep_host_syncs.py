"""The sweep's blocking host syncs (sweepHostSyncs), per train."""


def read(run):
    if not run.units or "sweepHostSyncs" not in run.counters:
        return None
    return run.counters["sweepHostSyncs"] / run.units
