"""OpStep wall CrossValidation, per train."""


def read(run):
    if not run.units or "CrossValidation" not in run.phases:
        return None
    return run.phases["CrossValidation"] / run.units
