"""Simulated requests completed per second: every lane's requests of
every Monte-Carlo call in the window, over the window's whole length
(the window ends with its last call)."""


def read(run):
    mc = [r for r in run.records if r["kind"] == "monte_carlo"]
    if not mc:
        return None
    return sum(r["lane_requests"] for r in mc) / (run.window_end - run.window_start)
