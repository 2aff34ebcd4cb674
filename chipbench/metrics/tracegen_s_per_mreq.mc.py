"""Trace-generation seconds per million simulated lane-requests: the
summed ``mc.trace`` spans (``Workload.sample`` of each lane, or each
chunk of a streamed trace drawn) of the window's Monte-Carlo calls."""

from chipbench import program_spans


def read(run):
    return program_spans.mc_seconds_per_mreq(run, "mc.trace")
