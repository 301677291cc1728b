"""Set-up seconds, process start to the window: start-up, data, commit,
warm-up (and, in a run that compiles, compilation).  Host clock."""


def read(run):
    return run.setup_s
