"""Programs compiled or loaded from the persistent cache while the window
ran (JAX monitoring events): every program the window runs should have
been made ready in set-up, so this is 0 in a sound run."""


def read(run):
    return float(run.programs_in_window)
