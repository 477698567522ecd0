"""Prompt tokens prefilled plus tokens generated, counted as each call's
result reaches the host, over the window's seconds."""


def read(run):
    if run.window_s <= 0:
        return None
    return run.tokens / run.window_s
