"""Run-route dispatches per query of the window: the program's
``kernel.grouped.runs`` counter (``plan/tpu_exec.py``: at every grouped
dispatch whose kernel reduces over runs of rows, each group one run of a
bucketed, sorted index, with no scatter) over the window's queries.
Nothing when the program has no run route."""

from hyperspace_tpu.plan import tpu_exec


def read(run):
    if not hasattr(tpu_exec, "_run_route") or not run.n:
        return None
    return run.counter("kernel.grouped.runs") / run.n
