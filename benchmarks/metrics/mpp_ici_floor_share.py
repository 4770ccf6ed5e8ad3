"""The exchange's share of its roofline: the least time chip 0's links could
take to carry the bytes that left it in the traced statements ((S-1)/S of what
`EXCHANGE_STATS` says a shard offered, over the published inter-chip bits/s),
as a share of the time chip 0 had a collective in flight
(`harness/mesh.py:reduce_mesh`, which tells a collective by its opcode:
`trace.py`'s `collective_s_chip0` goes by the instruction's name and does not
see JAX's `%all_to_all.N`).  The bound is bytes."""

from benchmarks.harness import mesh
from benchmarks.harness.peaks import peaks_for

SOURCE = "device_trace"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    stats, sent = mesh.exchange_stats(), mesh.statements_sent(run)
    got, n = mesh.of_run(run), mesh.traced_statements(run)
    if got is None or stats is None or not sent or not n:
        return None
    collective_s = got["collective_in_flight_s"]
    if not collective_s:
        return None
    out = mesh.ici_bytes_out(mesh.exchange_bytes_per_stmt(stats, sent) * n,
                             len(got["busy_s"]))
    peak = peaks_for(run.device["kind"])["ici_bits_per_s"]
    return 100.0 * mesh.ici_floor_s(out, peak) / collective_s
