"""OpStep walls ModelTraining + Evaluation (winner refit, train and
holdout evaluation), per train."""


def read(run):
    if not run.units or "ModelTraining" not in run.phases:
        return None
    return (run.phases["ModelTraining"]
            + run.phases.get("Evaluation", 0.0)) / run.units
