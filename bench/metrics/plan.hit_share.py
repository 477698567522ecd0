"""Share of the window's plan requests answered from the daemon's plan
cache (telemetry ``hits`` / ``requests``, window delta), in percent."""


def read(run):
    t = run.telemetry
    if not t or t["requests"] <= 0:
        return None
    return 100.0 * t["hits"] / t["requests"]
