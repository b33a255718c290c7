"""Find what a later PR adds as a file, by its name: no registry, no list.

``load("layer_metrics", "mfu_pct")`` imports
``chipbench/layer_metrics/mfu_pct.py`` by path (None when there is no such
file); ``load_all("kernels")`` imports every ``*.py`` of a directory.
"""

from __future__ import annotations

import glob
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        return None
    tag = f"chipbench_{kind}_{name}".replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_all(kind: str) -> dict:
    names = sorted(os.path.splitext(os.path.basename(p))[0]
                   for p in glob.glob(os.path.join(HERE, kind, "*.py")))
    return {n: load(kind, n) for n in names}
