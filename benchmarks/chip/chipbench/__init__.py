"""The chip benchmark's yardstick: traffic, reference, trace reduction and the
run harness. Nothing here imports the program except ``harness``, which drives
it; the reference and the reductions stand alone."""
import importlib.util


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints no line."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def load_file(path: str, name: str):
    """The Python file at ``path``, imported as a module named ``name``: how a
    metric's reader or a configuration's content is found by name."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
