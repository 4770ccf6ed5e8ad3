"""The system under test: an `Instance` behind a `MySQLServer` on port 0, as
`galaxysql_tpu/net/server.py:main` builds it, on a thread loop of the run
process.  Everything the benchmark sends goes through the socket."""

from __future__ import annotations

import asyncio
import threading

from benchmarks.harness.wire import WireClient


class ServedInstance:
    def __init__(self):
        from galaxysql_tpu.net.server import MySQLServer
        from galaxysql_tpu.server.instance import Instance
        # memory-only: the engine's AOT cache (<data_dir>/compile_cache) stays
        # detached; JAX's persistent cache is the one every run shares
        self.instance = Instance()
        self.server = MySQLServer(self.instance, "127.0.0.1", 0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True,
                                       name="mysql-server")
        self.thread.start()
        if not started.wait(30):
            raise RuntimeError("MySQLServer did not start listening in 30 s")

    @property
    def port(self) -> int:
        return self.server.port

    def connect(self, database=None) -> WireClient:
        return WireClient("127.0.0.1", self.port, database=database)

    def stop(self):
        asyncio.run_coroutine_threadsafe(self.server.stop(),
                                         self.loop).result(30)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(30)
        if self.thread.is_alive():
            raise RuntimeError("server thread did not stop")
