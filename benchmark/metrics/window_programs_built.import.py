"""window_programs_built.import: device programs compiled or loaded from
the persistent cache inside the measured window, from the program's
compile listeners (JAX's backend-compile events): 0 when the warm-up
blocks built every shape the window's blocks and sidecars use."""

from benchmark import program


def read(run):
    builds = program.builds_since(run.t0)
    if builds is None:
        return None
    return sum(1 for t, phase, _, _ in builds if phase == "compile" and t < run.t_end)
