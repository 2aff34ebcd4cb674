"""Device milliseconds of the working-set solver's executable per outer
iteration: its ``XLA Modules`` time in the traced window over the outer
iterations of the window's solves.

The solver's executable is the module with the most device time in a
window of working-set calls: on a TPU v5e it is named ``jit__unknown``
(the solver is jitted from a ``functools.partial``), beside small
``jit_convert_element_type`` and ``jit_less_equal`` executables."""


def read(run):
    if run.trace is None or not run.trace["modules_s"]:
        return None
    iters = sum(r["iterations"] for r in run.records if r["kind"] == "working_set")
    if not iters:
        return None
    return 1000.0 * max(run.trace["modules_s"].values()) / iters
