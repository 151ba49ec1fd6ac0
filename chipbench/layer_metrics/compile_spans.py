"""What the two ``train_compile*`` readers share: the in-window compile
spans that carry their site in their name.

The program's one ``jax.monitoring`` listener records every backend
compile as a retroactive span ``compile.program:<site>`` and every
persistent-cache load as ``compile.cache_load:<site>``; ``<site>`` is the
innermost ``compile_telemetry.building(...)`` block open at the time, or
``unattributed``. A program older than that names them all
``compile.program``, with no colon: nothing to read.
"""


def in_window(run):
    """``[(kind, site, seconds)]``, or ``None`` where no span carries a
    site."""
    out = []
    for t0, t1, name in run.spans:
        kind, colon, site = name.partition(":")
        if colon and kind in ("compile.program", "compile.cache_load"):
            out.append((kind, site, t1 - t0))
    return out or None
