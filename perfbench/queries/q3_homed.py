"""TPC-H Q3 as the ``sf1_mesh`` cell serves it: ``q3.py``'s query,
parameters, reference and comparison, on a deployment of several chips
whose served queries each run on a home chip
(``hyperspace_tpu.serve.context.query_home``). A program without home
chips cannot serve the cell as deployed: this file fails to load there,
at once, before any data is made, instead of building the cell's indexes
op by op for minutes and serving it through other routes."""

from hyperspace_tpu.serve.context import query_home  # noqa: F401  the requirement
from perfbench.queries.q3 import *  # noqa: F401,F403
