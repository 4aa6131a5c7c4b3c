"""XLA scatters per query of the window: the program's
``kernel.grouped.scatters`` counter (``plan/tpu_exec.py``: at every grouped
dispatch, the segment scatters the dispatched kernel holds) over the
window's queries. 0 where every grouped aggregate takes the scatter-free
Pallas histogram. Nothing when the program counts no scatters."""

from hyperspace_tpu.plan import tpu_exec


def read(run):
    if not hasattr(tpu_exec, "_grouped_scatters") or not run.n:
        return None
    return run.counter("kernel.grouped.scatters") / run.n
