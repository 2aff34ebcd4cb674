"""Chunk-feeding seconds per million simulated lane-requests: the summed
``mc.feed`` spans (stacking the lanes' chunks and moving them to the
device) of the window's Monte-Carlo calls."""

from chipbench import program_spans


def read(run):
    return program_spans.mc_seconds_per_mreq(run, "mc.feed")
