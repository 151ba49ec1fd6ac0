"""What the three ``sweep_*device*`` readers share: the in-window
``sweep.device`` spans with their attributes.

The selector stamps one such span a sweep program (a fold-stacked family, a
tree depth group's lane chunk) as it walks its one settle barrier in
dispatch order: from ``max(previous program ready, own dispatch end)`` to
the program's own ready, on the host clock. ``run.spans`` keeps names and
times only, so each is matched by ``(t0, t1)`` to the program's recorder,
which still holds the attributes (``family``, ``unitKind``, ``depth``,
``lanes``, ``chunk``, ``group``, ``exact``).
"""

NAME = "sweep.device"


def in_window(run):
    """``[(seconds, attrs)]`` of the window's spans, or ``None`` where the
    program records none (a program older than the span)."""
    wanted = {(t0, t1) for t0, t1, name in run.spans if name == NAME}
    if not wanted:
        return None
    from transmogrifai_tpu.utils.tracing import recorder
    return [(s.t1 - s.t0, s.attrs) for s in recorder.spans
            if s.name == NAME and (s.t0, s.t1) in wanted]


def seconds_per_unit(run, unit_kind: str):
    spans = in_window(run)
    if spans is None or not run.units:
        return None
    return sum(sec for sec, attrs in spans
               if attrs.get("unitKind") == unit_kind) / run.units
