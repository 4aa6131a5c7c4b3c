"""Kernel dispatches on the busiest chip over the mean across the host's
chips: the program's ``device.dispatch.d<k>`` counters
(``utils/rpc_meter.py``'s dispatch funnel, one a kernel dispatched on chip
k of a deployment) over the window. 1.0 when every chip dispatches as
much. Nothing when the program counts no dispatch by chip."""

import jax


def read(run):
    counts = [run.counter(f"device.dispatch.d{k}")
              for k in range(jax.device_count())]
    if not sum(counts):
        return None
    return max(counts) * len(counts) / sum(counts)
