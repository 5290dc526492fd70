"""``granitemoe``: every layer is the GQA block with routed SwiGLU experts."""

from perfbench.arch._gqa import layer_gemms, program  # noqa: F401
