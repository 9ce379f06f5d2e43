"""setup_program_build_s.att: seconds the program spent building device
programs (tracing, lowering, compiling or loading from the persistent
cache) from the start of the run to the window, from its compile
listeners, which start with the run."""

from benchmark import program


def read(run):
    after = program.builds_since(run.t0)
    if after is None:
        return None
    return program.build_seconds_total() - sum(seconds for _, _, seconds, _ in after)
