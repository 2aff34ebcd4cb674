"""Mean milliseconds per working-set call in ``ws.collect`` spans: the
solution back to the host, hit rates and the Report."""

from chipbench import program_spans


def read(run):
    return program_spans.ws_ms_per_call(run, "ws.collect")
