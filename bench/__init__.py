"""On-chip benchmark of the certified LASSO service (see BENCHMARK.json)."""
import importlib.util
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def find(folder: str, name: str):
    """The module ``bench/<folder>/<name>.py``: a design generator, a kind
    of traffic, a loop, a kind of response or a metric's reader, found by
    the name a data file gives it."""
    path = HERE / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    key = "bench_" + f"{folder}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
