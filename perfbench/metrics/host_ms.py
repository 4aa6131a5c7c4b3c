"""Mean ``host`` phase per query of the window, ms: the query thread's self
time, its run from ``LEDGER.begin`` to ``finish`` less every attribution
phase charged on it (host operators of ``plan/executor.py``, Python around
the device calls, and blocking syncs outside ``device_get``), from the
attribution ledger's ``serve.query.phase.host_ms`` histogram. Nothing when
the program has no such phase."""

from hyperspace_tpu.telemetry import attribution


def read(run):
    if "host" not in attribution.PHASES or not run.n:
        return None
    return run.hist_sum("serve.query.phase.host_ms") / run.n
