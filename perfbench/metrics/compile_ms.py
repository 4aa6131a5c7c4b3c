"""Mean ``compile`` phase per query of the window, ms: XLA compiles and
persistent-cache loads that a query waited for, from JAX's
``backend_compile_duration`` event through the attribution ledger's
``serve.query.phase.compile_ms`` histogram (0 when set-up warmed every
kernel). Nothing when the program has no such phase."""

from hyperspace_tpu.telemetry import attribution


def read(run):
    if "compile" not in attribution.PHASES or not run.n:
        return None
    return run.hist_sum("serve.query.phase.compile_ms") / run.n
