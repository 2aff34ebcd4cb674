"""Share, in %, of the working-set solver's residual evaluations that
moved a bracket: the counter ``ws.residual_evals_useful`` (bracket-growth
and bisection steps that changed the bracket of at least one proxy)
over ``ws.residual_evals`` (every step of every inner solve), summed
over the window's calls."""

from chipbench import program_spans


def read(run):
    found = program_spans.calls(run)
    if found is None:
        return None
    useful = program_spans.total(found, "ws.residual_evals_useful")
    evals = program_spans.total(found, "ws.residual_evals")
    if useful is None or not evals:
        return None
    return 100.0 * useful / evals
