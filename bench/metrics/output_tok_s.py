"""LLM tokens the host received inside the window, per second of it."""


def read(run):
    return run.tokens_in_window / run.seconds
