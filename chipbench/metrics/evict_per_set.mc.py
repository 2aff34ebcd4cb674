"""Objects evicted per set that evicted: the program counters
``mc.evictions`` (objects evicted by the Report's recorded sets) over
``mc.sets`` (recorded sets that evicted at least one), summed over the
window's Monte-Carlo calls. It says how many eviction-loop iterations
the drive loop runs for one insert."""

from chipbench import program_spans


def read(run):
    found = program_spans.having(program_spans.calls(run), "mc.drive")
    if found is None:
        return None
    evictions = program_spans.total(found, "mc.evictions")
    sets = program_spans.total(found, "mc.sets")
    if evictions is None or not sets:
        return None
    return evictions / sets
