"""Lanes the device cache dropped to stay inside its budget since the process
started (`DeviceCache.evictions`); 0 where everything the statements read stays
resident, and nothing on a program that does not count them."""

SOURCE = "program_counter"
LAYER = "lane caches"
MOVES = "ap_geomean_s"
UNIT = "lanes"


def read(run):
    from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE as cache
    if "latencies_s" not in run.window:
        return None
    return getattr(cache, "evictions", None)
