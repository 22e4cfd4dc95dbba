"""One module per architecture, found by the name of the configuration's
plain reference (``"reference": "<name>"``): ``references/<name>.py`` is
the plain float32 forward, ``arch/<name>.py`` the rest of what the
benchmark knows of that architecture.  Each module provides

- ``lm_config(model, qc)``: the program's ``LMConfig`` of a configuration's
  ``model`` section; a key that neither the program nor the reference
  reads is an error that names it;
- ``make(seed, model, quant)``, ``shape_of(model, quant)``: the serving
  parameter tree from the seed, made on the device, and its shapes;
- ``reference_view(params, model)``: ``(top, layer)``, the callables the
  reference reads the same weights through;
- ``kernel_calls(config, calls, kernel)``, ``model_ops(config, calls)``:
  the work of the program calls the harness recorded (``work.py``).
"""
from __future__ import annotations

import importlib


def of(config: dict):
    """The architecture module of a configuration."""
    return importlib.import_module(f"{__name__}.{config['reference']}")


def refuse_unread(model: dict, read: set, reference_only: set):
    """Raise on a ``model`` key that neither the program nor the reference
    reads, so that no key is dropped without a word."""
    unread = sorted(set(model) - read - reference_only)
    if unread:
        raise ValueError(f"model keys read by neither the program nor the "
                         f"reference: {unread}")
