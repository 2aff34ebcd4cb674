"""Host seconds per million simulated lane-requests: each Monte-Carlo
call's wall time less its drive-loop time, which leaves trace
generation, chunk transfer and Report assembly."""


def read(run):
    mc = [r for r in run.records if r["kind"] == "monte_carlo"]
    if not mc:
        return None
    mreq = sum(r["lane_requests"] for r in mc) / 1e6
    return sum(r["t1"] - r["t0"] - r["drive_s"] for r in mc) / mreq
