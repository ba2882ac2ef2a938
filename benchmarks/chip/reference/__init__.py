"""Plain float32 references, one module per architecture family.

Each module gives ``DEPARTURES`` (where it follows the program rather
than the published model) and ``logits(w, cfg, tokens, fp8=False)``.
A configuration file names its module under ``"reference"``.
"""
