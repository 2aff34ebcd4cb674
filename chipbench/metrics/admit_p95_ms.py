"""95th percentile, nearest rank, of the caller's latency of every
working-set call in the window: ``Scenario.run()`` call to return, in
milliseconds."""

import math


def read(run):
    lat = sorted(r["t1"] - r["t0"] for r in run.records if r["kind"] == "working_set")
    if not lat:
        return None
    return 1000.0 * lat[math.ceil(0.95 * len(lat)) - 1]
