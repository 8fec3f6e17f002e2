"""Find a piece of the benchmark by the name a cell's own files give it,
and load it from its file: a data kind (lib/data_kinds/<kind>.py), a
request generator (lib/generators/<generator>.py), a call
(lib/calls/<call>.py), a per-layer metric (layer_metrics/<metric>.py). A
later PR adds any of them as a new file; no file that is there names them.
A piece imports what it shares from `lib` (benchmarks/ is on sys.path).
"""

from __future__ import annotations

import importlib.util
import os
import re
import threading

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what a cell's files mean where they give no name: the behaviour the
# benchmark had before the piece became a file of its own
DEFAULTS = {"lib/data_kinds": "zipf", "lib/generators": "trees"}
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_loaded: dict = {}
_lock = threading.RLock()   # a piece may load another while it loads


def load(folder: str, name: str | None = None):
    """The module benchmarks/<folder>/<name>.py, loaded once; the
    folder's default where `name` is None."""
    if name is None:
        name = DEFAULTS[folder]
    if not _NAME.match(name):
        raise ValueError(f"not a name: {name!r}")
    path = os.path.join(BENCH, folder, f"{name}.py")
    with _lock:
        if path not in _loaded:
            if not os.path.isfile(path):
                raise FileNotFoundError(
                    f"no {folder}/{name}.py: the name {name!r} in a cell's "
                    f"files needs that file beside the ones that are there")
            spec = importlib.util.spec_from_file_location(
                f"{folder.replace('/', '_')}_{name.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _loaded[path] = mod
        return _loaded[path]
