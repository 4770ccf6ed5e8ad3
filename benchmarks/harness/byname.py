"""Files found by their name: a configuration, kind, driver or metric is a file
of its own that nothing registers."""

import importlib.util
import os


def load_module(path: str):
    """The Python file at `path` as a module of its own."""
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks: no file {path}")
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
