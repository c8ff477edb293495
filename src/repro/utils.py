"""Small shared utilities."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root: src/repro/utils.py -> ../..
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (listed in .gitignore): the path is part of
    the cache key, so it must not move between runs.  Call it at the
    start of an entry point, never at import time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_map_pairs(fn, tree, *rest):
    """Map ``fn`` (returning a 2-tuple) over trees; return two trees.

    Unlike tree.map + tuple-indexing, this is safe for pytrees that
    themselves contain tuples/dicts at internal nodes.
    """
    leaves, treedef = jax.tree.flatten(tree)
    rest_leaves = [jax.tree.flatten(r)[0] for r in rest]
    outs = [fn(l, *(rl[i] for rl in rest_leaves)) for i, l in enumerate(leaves)]
    a = treedef.unflatten([o[0] for o in outs])
    b = treedef.unflatten([o[1] for o in outs])
    return a, b
