from apex_tpu.utils.backoff import backoff_sleep
from apex_tpu.utils.bits import uint_view_dtype
from apex_tpu.utils.compile_cache import enable_compile_cache
from apex_tpu.utils.fsio import fsync_dir, write_atomic
from apex_tpu.utils.tree import (
    tree_cast,
    tree_all_finite,
    tree_select,
    tree_zeros_like,
    tree_size,
    global_norm,
)

__all__ = [
    "tree_cast",
    "tree_all_finite",
    "tree_select",
    "tree_zeros_like",
    "tree_size",
    "global_norm",
    "backoff_sleep",
    "uint_view_dtype",
    "enable_compile_cache",
    "write_atomic",
    "fsync_dir",
]
