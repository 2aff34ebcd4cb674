"""Mean milliseconds per working-set call in ``ws.solve`` spans: host
wall time from the solver's dispatch to its outputs being ready. Less
the device's ``ws_ms_per_iter.admit`` times ``ws_outer_iters.admit``,
it leaves the dispatch overhead."""

from chipbench import program_spans


def read(run):
    return program_spans.ws_ms_per_call(run, "ws.solve")
