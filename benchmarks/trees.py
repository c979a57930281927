"""Parameter trees as flat {"layer/sub/leaf": array} dicts and back."""

from __future__ import annotations

import jax


def flat(tree) -> dict:
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def nested(flat_tree: dict) -> dict:
    tree = {}
    for name, leaf in flat_tree.items():
        node = tree
        *parents, last = name.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = leaf
    return tree
