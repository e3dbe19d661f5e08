"""Model families: what the benchmark knows of each, one module per family.

A configuration file names its family under `model.family`; a file without
the key is `dense`.  `of(conf)` imports `families/<family>.py`; a family that
no module names is an error that lists the known ones, never a fallback.
Each module exports:

* ``KEYS``: each `model` key of a configuration file -> the attribute of
  the program's `ModelConfig` that must equal it: every width the program
  must match.  The harness restates each one `ModelConfig` has a field for
  (`ModelSpec.reduced` shrinks every width that is not restated) and
  checks all of them;
* ``PUBLISHED``: each key of the source's published config -> its `model`
  key, and ``CONVENTIONS``: for a published key that a source may leave
  out, its value by the family's convention (tests/test_configs.py);
* ``check(cfg)``: whether the program's `ModelConfig` has the structure the
  reference computes (its family, attention and head options);
* ``weights(m, seed)``, ``logits(w, m, rows, precision)``: the plain
  reference, importing nothing of the program;
* ``request_flops(m, seq)``, ``stage_cost(m, seq, rows, layers, embeds,
  unembeds)``: operations and bytes from shapes.

`costs.peaks`, `costs.least_time` and `reference.top1_gaps` are shared by
every family.  A new family is a new module here and nothing else.
"""

from __future__ import annotations

import importlib
import pkgutil

DEFAULT = "dense"


def known() -> list[str]:
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def load(name: str):
    """The module of family `name`."""
    if name.isidentifier() and not name.startswith("_"):
        try:
            return importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as exc:
            if exc.name != f"{__name__}.{name}":
                raise  # the family's module exists, and failed to import
    raise ValueError(f"unknown model family {name!r}; known: {known()}")


def of(conf: dict):
    """The family module of a configuration file."""
    return load(conf["model"].get("family", DEFAULT))
