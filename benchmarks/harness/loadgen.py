#!/usr/bin/env python3
"""One load-generator process of a closed loop: `--clients` connections, each a
thread that sends its next operation as soon as the last one was answered and
checked.  It imports the benchmark's wire client and the deployment kind's
`operation` (statement and expected reply from the seed) and nothing of the
program, so no generator process ever touches JAX or the chip.

Protocol with the run process, one line each way:
  ->  READY                       all connections are open
  <-  RUN <record_from> <until>   loop until `until` (time.monotonic(), which
                                  all processes of one machine share), keeping
                                  the operations answered at or after
                                  `record_from`
  ->  DONE                        the loop has ended
  <-  QUIT                        write what was kept to --out and exit
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness.byname import load_module  # noqa: E402
from benchmarks.harness.wire import WireClient, WireError  # noqa: E402


class Kept:
    def __init__(self):
        self.lock = threading.Lock()
        self.lat_ms, self.gap_ms = [], []
        self.failed = 0
        self.first_error = ""

    def merge(self, lat, gap, failed, error):
        with self.lock:
            self.lat_ms += lat
            self.gap_ms += gap
            self.failed += failed
            if error and not self.first_error:
                self.first_error = error


def client_loop(client, operation, params, rng, record_from, until, kept):
    lat, gap, failed, error = [], [], 0, ""
    prev_done = None
    while True:
        sql, expected = operation(params, rng)
        sent = time.monotonic()
        try:
            ok = client.query(sql)[1] == expected
            if not ok and not error:
                error = f"wrong reply to {sql!r}"
        except WireError as e:
            ok = False
            error = error or f"{sql!r}: {e}"
        except OSError as e:  # the connection is gone: this client stops
            kept.merge(lat, gap, failed + 1, error or f"{sql!r}: {e!r}")
            return
        done = time.monotonic()
        if done >= until:
            break
        if done >= record_from:
            if ok:
                lat.append((done - sent) * 1e3)
            else:
                failed += 1
            if prev_done is not None:
                gap.append((sent - prev_done) * 1e3)
        prev_done = done
    kept.merge(lat, gap, failed, error)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--database", required=True)
    ap.add_argument("--deployment", required=True, help="path of the kind's file")
    ap.add_argument("--params", required=True, help="JSON file for operation()")
    ap.add_argument("--clients", type=int, required=True)
    ap.add_argument("--first-client", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    operation = load_module(args.deployment).operation
    with open(args.params) as f:
        params = json.load(f)
    clients = [WireClient("127.0.0.1", args.port, database=args.database,
                          timeout=120.0) for _ in range(args.clients)]
    kept = Kept()
    print("READY", flush=True)
    run = 0
    for line in sys.stdin:
        word = line.split()
        if not word or word[0] == "QUIT":
            break
        record_from, until = float(word[1]), float(word[2])
        threads = [threading.Thread(
            target=client_loop, daemon=True,
            args=(c, operation, params,
                  random.Random(repr((args.seed, args.first_client + i, run))),
                  record_from, until, kept))
            for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        run += 1
        print("DONE", flush=True)
    for c in clients:
        c.close()
    with open(args.out, "w") as f:
        json.dump({"lat_ms": kept.lat_ms, "gap_ms": kept.gap_ms,
                   "failed": kept.failed, "first_error": kept.first_error}, f)


if __name__ == "__main__":
    main()
