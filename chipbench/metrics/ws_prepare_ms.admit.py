"""Mean milliseconds per working-set call in ``ws.prepare`` spans: the
rates, object lengths, input checks and the inputs moved to the
device."""

from chipbench import program_spans


def read(run):
    return program_spans.ws_ms_per_call(run, "ws.prepare")
