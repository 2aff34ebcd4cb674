"""Working-set solves completed per second over the window's whole
length (the window ends with its last call)."""


def read(run):
    ws = [r for r in run.records if r["kind"] == "working_set"]
    if not ws:
        return None
    return len(ws) / (run.window_end - run.window_start)
