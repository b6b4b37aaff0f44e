"""Kernel building blocks (aggregation, join, sort, window, ...).

The shared byte-accounting helper lives here: the lane pytrees it walks
are the nested dict/tuple shapes the ops modules produce.
"""
from __future__ import annotations


def tree_nbytes(tree) -> int:
    """Total ``nbytes`` across every array leaf of a lane pytree.

    Accepts the nested dict/tuple/list shapes dispatches produce (output
    lane maps, ``(values, validity)`` pairs, check-scalar tuples); leaves
    without ``nbytes`` (python scalars, None validity) count as zero.
    """
    total = 0
    stack = [tree]
    while stack:
        node = stack.pop()
        if node is None:
            continue
        if isinstance(node, dict):
            stack.extend(node.values())
        elif isinstance(node, (tuple, list)):
            stack.extend(node)
        else:
            nb = getattr(node, "nbytes", None)
            if nb is not None:
                total += int(nb)
    return total
