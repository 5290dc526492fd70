"""One adapter per architecture: ``perfbench/arch/<model_type>.py``.

``run.load_cell`` loads the adapter named by the configuration file's
``model_type``, by its file path, so a new architecture is a new file and
no edit here. An adapter exposes two functions:

``layer_gemms(cfg, batch, seq, phase) -> [(name, M, K, N), ...]``
    The plain reference of the layer equations: every projection GEMM of
    the stack as modelled, layer by layer, then any head. It imports
    nothing of ``repro``; ``check.py`` holds the program's GEMMs and
    answers against it.
``program(cfg) -> (ModelConfig, CompileOptions)``
    What the timed path compiles the configuration with; it imports
    ``repro`` inside the function.

Modules whose names start with ``_`` hold blocks that adapters share.
"""
