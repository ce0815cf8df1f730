"""Model FLOPs of a training sample, from the parameter shapes alone.

6 x N x T per sample of T tokens (forward 2, backward 4), where N counts
every parameter but an input embedding table whose rows are only
gathered; a table tied to the output projection multiplies, and counts. Recomputation under remat
and the attention score products are not counted, so a share of the peak
built on this never credits work the model does not need.
"""
from __future__ import annotations

import math
from typing import Any, Iterable, Tuple


def matmul_params(leaves: Iterable[Tuple[str, Tuple[int, ...]]],
                  tied: bool = False) -> int:
    """Parameters that multiply activations."""
    return sum(math.prod(shape) for path, shape in leaves
               if tied or path != "embed")


def train_flops_per_sample(leaves: Iterable[Tuple[str, Tuple[int, ...]]],
                           seq_len: int, tied: bool = False) -> float:
    """``leaves``: (top-level key, shape) of every parameter leaf."""
    return 6.0 * matmul_params(leaves, tied) * seq_len


def param_leaves(tree: Any):
    """(top-level key, shape) of each leaf of a param tree of arrays or
    ShapeDtypeStructs."""
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append((str(getattr(path[0], "key", path[0])), tuple(leaf.shape)))
    return out
