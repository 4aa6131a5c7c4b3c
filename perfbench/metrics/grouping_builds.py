"""Groupings built per query of the window: the program's
``pipeline.grouping.builds`` counter (``plan/tpu_exec.py``: a grouped
aggregate that concatenated and factorized its key chunks, and uploaded
their group ids, instead of taking the grouping cached for those chunks)
over the window's queries. 0 when set-up built the grouping of every chunk
set the window groups. Nothing when the program caches no grouping."""

from hyperspace_tpu.plan import tpu_exec


def read(run):
    if not hasattr(tpu_exec, "_grouping") or not run.n:
        return None
    return run.counter("pipeline.grouping.builds") / run.n
