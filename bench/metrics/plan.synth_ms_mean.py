"""Mean milliseconds of one plan synthesis in the daemon during the
window (telemetry ``synthesis.seconds_sum`` / ``count``, window delta;
cold, warm repair, upgrade and prewarm syntheses alike)."""


def read(run):
    t = run.telemetry
    if not t or t["synth_count"] <= 0:
        return None
    return 1e3 * t["synth_seconds"] / t["synth_count"]
