"""Roofline share of the device fill of the free-text columns' blocks.

Device time: every leaf op of the ``_fill_text_entries*`` programs (the
block's zero fill with its length and null columns, and the scatter of each
piece of entries), summed from the trace. Against it, the least a dense
fill needs (``opcount_text.text_fill``): ONE write of ``n x width x 4``
bytes a filled block. Bound by memory. A program without those programs
reports nothing.
"""

from chipbench import opcount, opcount_text

MODULE = "jit__fill_text_entries"


def is_text_fill(name: str) -> bool:
    return name.partition("/")[0].startswith(MODULE)


def read(run):
    if run.trace is None or not run.peaks or \
            "text_blocks" not in (run.shapes or {}):
        return None
    seconds = run.trace.kernel_seconds(is_text_fill)
    flops, bytes_ = opcount_text.text_fill(run.shapes)
    least = opcount.roofline_seconds(flops * run.units, bytes_ * run.units,
                                     run.peaks)
    return 100.0 * least / seconds if seconds > 0 and least > 0 else None
