"""Queries whose bundle came back, over the time from the first
submission to the last completion.  Host clock."""


def read(run):
    span = run.window_end - run.window_start
    return len(run.completed) / span if run.completed and span > 0 else None
