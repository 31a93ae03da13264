"""JAX's persistent compilation cache, placed from outside.

One helper for the scripts that reach the chip (``chip_smoke.py``,
``bench.py``), called before the first compile.  A chip call starts
with no compiled code; where the machine hands later calls the same
``JAX_COMPILATION_CACHE_DIR``, what one call compiled the next finds.

JAX's cache keeps what the COMPILER made and is keyed by the lowered
program, so a process still traces and lowers every program it runs: for
a serving step seconds of Python a shape, most of them inside the
kernels' bodies.  ``kept_lowering`` keeps that half too, beside JAX's
entries, for a program that is compiled at several shapes before it
serves (the engine's ladder of step sizes, inference/serving.py).
"""

from __future__ import annotations

import functools
import hashlib
import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it:
    it is left alone and no other directory is set in code.  Where it
    is not, the cache lives at ``<checkout>/.jax_cache`` — a fixed path
    (the path is part of the cache key, so a directory that moves never
    hits).  Every program is kept, however fast it compiled."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


@functools.lru_cache(maxsize=None)
def _source_fingerprint() -> str:
    """A hash of every file of this package (sources and the data they
    read): what a lowering was traced from, so a lowering kept by
    another version of the code is never found."""
    h = hashlib.sha256()
    root = pathlib.Path(__file__).resolve().parents[1]
    for path in sorted(p for p in root.rglob("*") if p.is_file()
                       and "__pycache__" not in p.parts
                       and p.suffix not in (".pyc", ".so", ".o")):
        h.update(str(path.relative_to(root)).encode())
        h.update(path.read_bytes())
    return h.hexdigest()


def kept_lowering(fn, args, kwargs, static, *, donate_argnums=(),
                  donate_argnames=(), what=()):
    """``fn`` (a jitted function) at the shapes of ``args`` / ``kwargs``
    and the static arguments ``static``, as a function of ``(*args,
    **kwargs)`` that does not trace ``fn`` again in a process that finds
    the lowering kept.

    With no persistent cache directory configured (or the cache turned
    off) this is ``fn`` with ``static`` bound.  With one, the lowering
    (``jax.export``: the StableHLO module, kernels and scope names
    included) is kept in that directory under a key of everything it
    was traced from: this
    package's sources, JAX's version and configuration, the backend and
    its device, ``what`` (the caller's part: a model configuration), and
    the arguments' tree, shapes and types.  A later process calls the
    kept module through a ``jax.jit`` of its own, donating as told, so
    the COMPILED program comes from JAX's own cache as ever; a file that
    cannot be read back is made again."""
    import jax.export
    import jaxlib

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not jax.config.jax_enable_compilation_cache:
        return functools.partial(fn, **static)
    spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        (tuple(args), dict(kwargs)))
    leaves, tree = jax.tree.flatten(spec)
    dev = jax.devices()[0]
    key = hashlib.sha256(repr((
        _source_fingerprint(), jax.__version__, jaxlib.__version__,
        dev.platform, dev.device_kind, sorted(jax.config.values.items()),
        getattr(fn, "__qualname__", repr(fn)), sorted(static.items()),
        donate_argnums, donate_argnames, what, str(tree),
        [(x.shape, str(x.dtype)) for x in leaves])).encode()).hexdigest()
    path = pathlib.Path(cache_dir) / f"paddle_tpu-lowering-{key[:40]}"
    exported = None
    if path.exists():
        try:
            exported = jax.export.deserialize(bytearray(path.read_bytes()))
        except Exception:  # noqa: BLE001 - unreadable: made again below
            pass
    if exported is None:
        exported = jax.export.export(fn)(*spec[0], **spec[1], **static)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
        tmp.write_bytes(exported.serialize())
        os.replace(tmp, path)

    return jax.jit(exported.call, donate_argnums=donate_argnums,
                   donate_argnames=donate_argnames)
