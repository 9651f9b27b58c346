"""The benchmark's own tests: run on the CPU, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(committees_per_slot=4, committee_size=16,
            aggregators_per_committee=2, subscribed_subnets=2, validators=64,
            block_aggregates=8, sync_committee_size=8)
