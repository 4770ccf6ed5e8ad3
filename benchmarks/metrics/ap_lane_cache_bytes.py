"""Bytes of column lanes resident in the device cache when the readers run, the
window's end (`GLOBAL_DEVICE_CACHE`'s own count, as its gauge
`device_cache_bytes` shows it): what the cell's statements keep in HBM between
executions."""

SOURCE = "program_counter"
LAYER = "lane caches"
MOVES = "ap_geomean_s"
UNIT = "bytes"


def read(run):
    from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE as cache
    if "latencies_s" not in run.window:
        return None
    return int(cache._bytes)
