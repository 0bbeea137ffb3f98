"""Process start to the first due request: building the model, warming
every shape the window uses, and any compiling (host clock)."""


def read(run):
    return run.setup_s
