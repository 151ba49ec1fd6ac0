"""OpStep walls DataReadingAndFiltering + FeatureEngineering, per train."""


def read(run):
    if not run.units or "FeatureEngineering" not in run.phases:
        return None
    return (run.phases.get("DataReadingAndFiltering", 0.0)
            + run.phases["FeatureEngineering"]) / run.units
