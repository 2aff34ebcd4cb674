"""Drive-loop seconds per million simulated lane-requests: the sum of
``Report.elapsed_s`` (host clock around the compiled drive loop, ended
by ``block_until_ready``) of the window's Monte-Carlo calls."""


def read(run):
    mc = [r for r in run.records if r["kind"] == "monte_carlo"]
    if not mc:
        return None
    mreq = sum(r["lane_requests"] for r in mc) / 1e6
    return sum(r["drive_s"] for r in mc) / mreq
