from repro.utils.tree import (
    tree_size,
    tree_bytes,
    tree_global_norm,
    tree_zeros_like,
    tree_add,
    tree_scale,
)
from repro.utils.logging import get_logger
from repro.utils.compile_cache import use_compile_cache

__all__ = [
    "tree_size",
    "tree_bytes",
    "tree_global_norm",
    "tree_zeros_like",
    "tree_add",
    "tree_scale",
    "get_logger",
    "use_compile_cache",
]
