"""Rows the device selection evaluated its predicate over, per query of the
window: the program's ``select.rows_scanned`` counter (``plan/tpu_exec.py``,
the selection route) over the window's queries. Nothing when the program has
no selection route."""

from hyperspace_tpu.plan import tpu_exec


def read(run):
    if not hasattr(tpu_exec, "try_execute_select") or not run.n:
        return None
    return run.counter("select.rows_scanned") / run.n
