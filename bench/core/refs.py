"""Loading a configuration's plain reference, the ``.py`` file beside its
``.json`` in ``bench/configs/``."""
from __future__ import annotations

import importlib.util


def config_module(ctx):
    name = ctx.config["name"]
    path = ctx.root / "bench" / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_ref_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
