"""Queries homed on the busiest chip over the mean across the host's chips:
the program's ``serve.home.d<k>`` counters (``serve/scheduler.py``, one a
query homed on chip k of its deployment) over the window. 1.0 when the
scheduler spreads the window's queries evenly. Nothing when the program
homed no query."""

import jax


def read(run):
    homes = [run.counter(f"serve.home.d{k}")
             for k in range(jax.device_count())]
    if not sum(homes):
        return None
    return max(homes) * len(homes) / sum(homes)
