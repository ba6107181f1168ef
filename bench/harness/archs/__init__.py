"""One module per architecture, found by the ``model_type`` key of a
configuration file: ``bench/harness/archs/<model_type>.py``. A new
architecture is a new module here and its configuration; no other file of
the harness names one.

A module gives:

  SCOPES          the program's train-step scopes this architecture adds to
                  ``harness.scopes.COMMON`` (``jax.named_scope`` names)
  COUNTERS        names of further ``Trainer`` counters the run hands on
                  (as ``program.<name>`` in ``Outcome.counters``)
  arch_config(model, *, dtype, param_dtype)
                  the program's ``ArchConfig`` for the configuration
  layout(model)   every weight as {path: ``harness.weights.Leaf``}, in the
                  order its values are drawn from the seed
  row_loss(model, params, masks, tokens, targets, quant, chunk)
                  the plain float32 summed next-token loss of one row, the
                  reference the program is compared with; it imports
                  nothing of the program
  flops_per_step(model, rows, seq, counters)
                  operations one training step of ``rows`` x ``seq`` tokens
                  requires (``harness.cost``); ``counters`` are the
                  window's, for work that depends on the data
"""
from __future__ import annotations

import importlib


def of(model: dict):
    """The module of a configuration's ``model_type``."""
    return importlib.import_module(f"harness.archs.{model['model_type']}")
