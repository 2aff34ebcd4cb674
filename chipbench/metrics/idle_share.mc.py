"""Share of the traced window, in %, in which no op ran on the device,
in a window of Monte-Carlo calls."""


def read(run):
    if run.trace is None or not any(r["kind"] == "monte_carlo" for r in run.records):
        return None
    return 100.0 * run.trace["idle_share"]
