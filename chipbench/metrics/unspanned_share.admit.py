"""Share of the window's working-set calls, in %, that no program span
covers: the self time of each ``scenario.run`` span (its duration less
the union of its child spans) over the summed ``scenario.run`` time."""

from chipbench import program_spans


def read(run):
    found = program_spans.having(program_spans.calls(run), "ws.solve")
    if found is None:
        return None
    wall = sum(root.t1 - root.t0 for root, _ in found)
    return 100.0 * sum(program_spans.self_seconds(*c) for c in found) / wall
