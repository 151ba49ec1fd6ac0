"""Share of the rows given to the window's forest programs that their
growers carried: ``100 * forestRowsCarried / forestRowsTotal``, both counted
in rows times trees where a forest program is dispatched. About 65 where a
forest round carries only the rows its Poisson(1) bootstrap drew; 100 would
mean every tree moved all its rows. A program older than the counters, or a
run without a forest, reports nothing."""


def read(run):
    total = run.counters.get("forestRowsTotal")
    if not total or "forestRowsCarried" not in run.counters:
        return None
    return 100.0 * run.counters["forestRowsCarried"] / total
