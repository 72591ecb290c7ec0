"""Configuration files -> what the program and the yardstick need.

A configuration file holds the published keys of its source at the top
level (as run: a key named in `reduced` carries the reduced value and
`published` the original), and nested groups for what the benchmark
sets itself (`engine` or `trainer`, `dtype`, `program`, `sizing`,
`assumed`, `departures`). What depends on the model's family is in
benchmark/adapters/<model_type>.py.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict


def load_config(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        conf = json.load(f)
    for key in ("source", "model_type", "reduced", "chips"):
        if key not in conf:
            raise ValueError(f"{os.path.basename(path)}: missing key {key!r}")
    return conf


def adapter(conf: Dict[str, Any]):
    try:
        return importlib.import_module(f"benchmark.adapters.{conf['model_type']}")
    except ModuleNotFoundError:
        raise ValueError(f"model_type {conf['model_type']!r}: no adapter "
                         "(benchmark/adapters/) and no reference") from None


def transformer_config(conf: Dict[str, Any]):
    """The program's model configuration for a configuration file."""
    import jax.numpy as jnp

    dtypes = conf.get("dtype", {})
    return adapter(conf).program_config(
        conf,
        dtype=jnp.dtype(dtypes.get("compute", "bfloat16")),
        param_dtype=jnp.dtype(dtypes.get("params", "float32")),
        **conf.get("program", {}),
    )


def shape_numbers(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the cost functions in roofline.py take."""
    return adapter(conf).shapes(conf)
