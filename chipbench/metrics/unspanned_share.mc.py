"""Share of the host path of the window's Monte-Carlo calls, in %, that
no program span covers: the self time of each ``scenario.run`` span
(its duration less the union of its child spans) over the summed
``scenario.run`` less ``mc.drive`` time."""

from chipbench import program_spans


def read(run):
    found = program_spans.having(program_spans.calls(run), "mc.drive")
    if found is None:
        return None
    host = sum(root.t1 - root.t0 for root, _ in found) - program_spans.seconds(found, "mc.drive")
    if host <= 0:
        return None
    return 100.0 * sum(program_spans.self_seconds(*c) for c in found) / host
