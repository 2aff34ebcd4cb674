"""Mean outer (damped-Jacobi) iterations per working-set solve, from
``Report.extras["iterations"]``."""


def read(run):
    ws = [r["iterations"] for r in run.records if r["kind"] == "working_set"]
    if not ws:
        return None
    return sum(ws) / len(ws)
