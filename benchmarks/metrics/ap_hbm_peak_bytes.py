"""`memory_stats()` peak_bytes_in_use after the window, on the fullest chip."""

SOURCE = "program_counter"
LAYER = "device"
MOVES = "ap_geomean_s"
UNIT = "bytes"


def read(run):
    if "latencies_s" not in run.window or not run.device.get("memory_peak_bytes"):
        return None
    return run.device["memory_peak_bytes"]
