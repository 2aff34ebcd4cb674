"""Collection seconds per million simulated lane-requests: the summed
``mc.collect`` spans (the engine state back to the host, occupancy
assembly, hit rates and the Report) of the window's Monte-Carlo
calls."""

from chipbench import program_spans


def read(run):
    return program_spans.mc_seconds_per_mreq(run, "mc.collect")
