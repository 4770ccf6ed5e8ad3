"""Microseconds the program's bulk path spent a row it appended
(`storage/table_store.py:LOAD_STATS`: the seconds of encode, route and append
over the rows, since the process started); nothing on a program without the
counter."""

SOURCE = "program_span"
LAYER = "storage"
MOVES = "setup_s"
UNIT = "us/row"


def read(run):
    from galaxysql_tpu.storage import table_store
    stats = getattr(table_store, "LOAD_STATS", None)
    if not stats or not stats.get("rows"):
        return None
    seconds = stats["encode_s"] + stats["route_s"] + stats["append_s"]
    return seconds / stats["rows"] * 1e6
