"""Process start to window start: imports, model, compiles or cache
loads, the engine's warm-up and any warm-up traffic."""


def read(run):
    return run.setup_s
