"""``qwen3``: every layer is the GQA + dense SwiGLU block."""

from perfbench.arch._gqa import layer_gemms, program  # noqa: F401
