"""Shared kernel-runtime policy: when do Pallas kernels interpret?

Every Pallas kernel in this package takes ``interpret: bool | None``. None
(the default everywhere) means "decide from the backend": compile natively
on accelerators that can lower Mosaic/Triton (TPU, GPU), interpret on
everything else (CPU CI, the common case for this repo's tests). An
explicit bool always wins — tests pin ``interpret=True`` for determinism,
TPU runs may force ``interpret=False`` to fail loudly if lowering breaks.
No wrapper defaults to ``True``: on a TPU a kernel lowers, it never
interprets.

The resolved value is part of the engine's compiled-plan cache key
(``PallasSubstrate.cache_fingerprint``); ``jax.default_backend()`` is fixed
for the life of a process, so the resolution is too.
"""
from __future__ import annotations

# backends whose Pallas lowering is native; everything else interprets
_COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def default_interpret(backend: "str | None" = None) -> bool:
    """True when Pallas kernels should run in interpret mode here."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    return backend not in _COMPILED_BACKENDS


def resolve_interpret(interpret: "bool | None") -> bool:
    """The per-call resolution every kernel wrapper funnels through."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)
