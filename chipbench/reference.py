"""The plain reference of a cell's configuration, from its family.

Each family's ``reference.py`` (``families/<family>/``) is the
configuration's forward pass in plain float32 ``jax.numpy`` at ``highest``
precision, over the benchmark's own weights, with no cache, no batching of
requests into slots and no kernels; it imports nothing of the program.
``quant="fp8"`` is its control: the same pass with the inputs of every
weight matrix product rounded to float8 e4m3.  This module hands each call
to the family that the configuration names.
"""
from __future__ import annotations

import spec


def hidden(w, conf, tokens, quant=None):
    """Final normed hidden states (B, T, D) in float32 for ``tokens``."""
    return spec.family(conf, "reference").hidden(w, conf, tokens, quant)


def head_stats(w, conf, x, targets, quant=None):
    """For each position: the best logit, the logit of ``targets`` there
    and the top token.  x: (B, T, D) float32, targets: (B, T)."""
    return spec.family(conf, "reference").head_stats(w, conf, x, targets,
                                                     quant)
