"""Device: peak bytes in use on the fullest chip."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
