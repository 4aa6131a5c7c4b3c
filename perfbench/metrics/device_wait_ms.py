"""Mean ``device_wait`` phase per query of the window, ms: the query thread
blocked in ``utils/rpc_meter.device_get`` until the device finishes what it
fetches (``jax.block_until_ready``), from the attribution ledger's
``serve.query.phase.device_wait_ms`` histogram. Nothing when the program
has no such phase."""

from hyperspace_tpu.telemetry import attribution


def read(run):
    if "device_wait" not in attribution.PHASES or not run.n:
        return None
    return run.hist_sum("serve.query.phase.device_wait_ms") / run.n
