"""Mean ``fetch`` phase per query of the window, ms: the device-to-host copy
of ready arrays in ``utils/rpc_meter.device_get``, from the attribution
ledger's ``serve.query.phase.fetch_ms`` histogram. Nothing when the program
does not split the device wait out of its fetch (there ``fetch`` holds
both)."""

from hyperspace_tpu.telemetry import attribution


def read(run):
    if "device_wait" not in attribution.PHASES or not run.n:
        return None
    return run.hist_sum("serve.query.phase.fetch_ms") / run.n
