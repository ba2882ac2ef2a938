"""Model families: how a configuration file maps onto the program.

A configuration file (``configs/``) names its family under ``"family"``,
as it names its reference under ``"reference"``; the harness imports
``families.<family>`` and knows no architecture key itself.  A family
module gives:

- ``model_config(cfg)``: the program's ``repro.configs.base.ModelConfig``;
- ``weight_shapes(cfg)``: ``{name: (shape, scale)}`` of every weight in
  the benchmark's own layout, which ``weights.canonical`` draws and the
  reference reads.  ``scale`` is ``None`` for a norm scale, a float for
  an embedding's standard deviation, an int for a dense matrix's fan-in;
- ``program_params(cfg, w, padded_vocab)``: those weights as the tree
  ``repro.models.lm`` takes: ``blocks``, one stack per kind of the layer
  pattern over its repeats, and ``tail``, the layers past the last whole
  repeat;
- ``tiny(cfg)``: the model at rehearsal size for the CPU, with a whole
  period of the layer pattern kept and experts still routed top-k;
- ``prefill_flops(cfg, s)`` and ``decode_flops(cfg, keys)``: useful FLOPs
  of one prompt of ``s`` tokens and of one decode token at ``keys`` keys,
  by layer kind, of the experts this chip holds.

A family imports the program (``repro``) and JAX inside its functions:
the harness loads it before it has put the program on the path.
"""

from __future__ import annotations

import importlib


def of(cfg: dict):
    """The family module that the configuration names."""
    return importlib.import_module(f"{__name__}.{cfg['family']}")
