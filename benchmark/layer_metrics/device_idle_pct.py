"""device: 1 - busy / the profile's own window, which is ONE job's encode
stage (profiler start and stop included), not the run's window."""


def read(ev):
    prof = ev["profile"]
    if not prof or not prof["window_s"]:
        return None
    return 100.0 * (1.0 - prof["busy_s"] / prof["window_s"])
