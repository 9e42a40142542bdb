"""Shared small utilities used across the framework."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

# Canonical dtype registry (string names keep configs JSON-serializable).
DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "int8": jnp.int8,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}


def dtype_of(name: str) -> jnp.dtype:
    return DTYPES[name]


def tree_paths(tree: Any, prefix: tuple = ()) -> Iterator[tuple[tuple, Any]]:
    """Yield (path, leaf) for a nested dict/list pytree of leaves."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from tree_paths(tree[k], prefix + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from tree_paths(v, prefix + (str(i),))
    else:
        yield prefix, tree


def tree_map_with_path(fn, tree: Any, prefix: tuple = ()) -> Any:
    if isinstance(tree, dict):
        return {k: tree_map_with_path(fn, v, prefix + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        typ = type(tree)
        return typ(tree_map_with_path(fn, v, prefix + (str(i),)) for i, v in enumerate(tree))
    return fn(prefix, tree)


def param_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def param_bytes(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def fold_path(key: jax.Array, path: tuple) -> jax.Array:
    """Derive a deterministic per-parameter rng key from a path."""
    h = 0
    for part in path:
        for ch in str(part):
            h = (h * 131 + ord(ch)) % (2**31 - 1)
    return jax.random.fold_in(key, h)


class NpEncoder(json.JSONEncoder):
    def default(self, obj):
        if isinstance(obj, (np.integer,)):
            return int(obj)
        if isinstance(obj, (np.floating,)):
            return float(obj)
        if isinstance(obj, np.ndarray):
            return obj.tolist()
        if dataclasses.is_dataclass(obj):
            return dataclasses.asdict(obj)
        return super().default(obj)


def dump_json(obj: Any, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, cls=NpEncoder)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already keeps the cache there and no directory is set here; otherwise
    it is the fixed ``<repo>/.jax_cache`` (a fixed path: the path is part
    of the cache key, so a moving directory would never hit). Every
    compile is cached, however short. Call it from an entry point, never
    at import time."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


@contextlib.contextmanager
def compile_cache_off():
    """Keep JAX's persistent compilation cache out of a block, so that a
    block which times compiles measures compiles, not cache reads."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
