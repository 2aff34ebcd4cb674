"""Share of the traced window, in %, in which no op ran on the device,
in a window of working-set calls."""


def read(run):
    if run.trace is None or not any(r["kind"] == "working_set" for r in run.records):
        return None
    return 100.0 * run.trace["idle_share"]
