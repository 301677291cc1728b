"""Pluggable compute backends for the prover hot loops.

One semantic spec, three interchangeable implementations (the structure
hardware-accelerated ZK systems use — cf. PAPERS.md on GPU PLONKish
proving): every backend computes the *same field elements bit-for-bit*, so
proof transcripts are identical across backends and Fiat–Shamir challenges
cannot diverge.  The suite asserts this parity (``tests/test_backend.py``).

Backends
--------
``ref``
    The pure-jnp reference paths that shipped with the seed
    (``hashing.permute_ref``, ``poly.ntt_ref``, a ``jax.lax``
    associative scan for the grand product).  Default; fastest on CPU.
``pallas-interpret``
    The Pallas kernels under ``repro.kernels`` executed with
    ``interpret=True`` — runs anywhere (CI, CPU containers) and exercises
    the exact kernel code paths, so kernel drift against the reference is
    caught on every PR without accelerator hardware.
``pallas``
    The same kernels compiled for the TPU (``interpret=False``).  Raises at
    dispatch time on a CPU-only host; :func:`require` checks it runs, and
    gives its permutation bit for bit, before a run relies on it.

Selection
---------
Resolution order for the active backend (first hit wins):

1. an explicit :func:`use` scope (a context manager; nests, restores),
2. the ``ZKGRAPH_BACKEND`` environment variable,
3. the default, ``ref``.

``ProverConfig.backend`` (compare-excluded, never serialized: a backend is
an execution detail, not a proof parameter) routes a whole
``keygen``/``prove`` call through :func:`use` so sessions can pin a backend
per configuration.  The keygen cache key incorporates the resolved backend
name (:func:`resolve_name`) so PK/LDE caches never cross backends.

The dispatched primitives
-------------------------
``permute``
    Batched Poseidon-like permutation, ``(..., 16) -> (..., 16)`` — the
    Merkle/sponge workhorse (``hashing.permute`` and everything above it:
    ``hash_rows``, ``hash_bytes``, ``merkle.commit`` level builds).
``ntt``
    Radix-2 NTT along the last axis, natural order, ``inverse=`` for the
    scaled inverse transform — ``poly.ntt``/``intt``/``coset_lde``.
``grand_product_ext``
    Exclusive running product of Fp4 elements, ``(n, 4) -> (n, 4)`` with
    ``Z[0] = 1`` — the paper's Eq. (2) accumulator in the prover's phase-2
    ext-column construction.

Kernel-facing shape adapters (padding to tile multiples) live in each
kernel's ``ops.py``; this module only routes.
"""
from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Callable, Optional

ENV_VAR = "ZKGRAPH_BACKEND"
DEFAULT = "ref"


class UnknownBackendError(ValueError):
    """Asked for a backend name that was never registered."""


@dataclass(frozen=True)
class ComputeBackend:
    """One named implementation of the prover's compute primitives."""
    name: str
    description: str
    permute: Callable          # (..., 16) uint32 -> (..., 16)
    ntt: Callable              # (..., n), inverse=False -> (..., n)
    grand_product_ext: Callable  # (n, 4) -> (n, 4) exclusive Fp4 products
    interpret: Optional[bool]  # Pallas interpret flag; None = pure jnp


_REGISTRY: dict = {}
# explicit use() stacks, innermost last — PER THREAD.  A proving service
# runs concurrent pipeline workers; a shared stack would interleave their
# push/pops and corrupt every thread's selection, so each thread gets its
# own.  Consequence: a worker thread does NOT inherit the spawning thread's
# scope — cross-thread pinning must be explicit (resolve_name() in the
# submitting thread, use(name) in the worker; ProofService does exactly
# this, and Keys.backend does it for keygen/prove).
_TLS = threading.local()


def _scopes() -> list:
    scopes = getattr(_TLS, "scopes", None)
    if scopes is None:
        scopes = _TLS.scopes = []
    return scopes


def register(backend: ComputeBackend) -> ComputeBackend:
    _REGISTRY[backend.name] = backend
    return backend


def names() -> tuple:
    return tuple(_REGISTRY)


def get(name: str) -> ComputeBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownBackendError(
            f"unknown compute backend {name!r}; available: "
            f"{', '.join(_REGISTRY)}") from None


def active_name() -> str:
    """The currently selected backend name (this thread's scope > env var >
    default)."""
    scopes = _scopes()
    if scopes:
        return scopes[-1]
    env = os.environ.get(ENV_VAR)
    if env:
        get(env)               # validate eagerly: typos fail loudly
        return env
    return DEFAULT


def active() -> ComputeBackend:
    return get(active_name())


def resolve_name(name: str = None) -> str:
    """A concrete backend name: ``name`` if given (validated), else the
    active selection.  This is the keygen-cache key component."""
    if name is not None:
        get(name)
        return name
    return active_name()


@contextlib.contextmanager
def use(name: str = None):
    """Pin the active backend within a ``with`` block (nests, restores).

    The pin is *thread-local*: concurrent pipeline workers can each pin a
    backend without perturbing one another, and a scope entered on one
    thread is invisible to every other (pass ``resolve_name()`` across the
    thread boundary to hand a selection over).

    ``name=None`` pins whatever is active at entry — used by
    ``keygen``/``prove`` to freeze ``cfg.backend`` resolution for the whole
    call even if the environment changes mid-proof."""
    scopes = _scopes()
    scopes.append(resolve_name(name))
    try:
        yield _REGISTRY[scopes[-1]]
    finally:
        scopes.pop()


class BackendUnavailableError(RuntimeError):
    """A backend that was asked for explicitly cannot run on this host."""


def probe(name: str) -> tuple:
    """(usable, reason) — run a small permutation under ``name`` and compare
    it with the reference bit for bit.

    The compiled ``pallas`` backend needs a TPU; on a CPU host it raises at
    lowering time, which this turns into an availability answer."""
    import numpy as np
    try:
        be = get(name)
        states = np.arange(2 * 16, dtype=np.uint32).reshape(2, 16) * 7919
        with use(name):
            out = np.asarray(be.permute(states))
        from . import hashing
        want = np.asarray(hashing.permute_ref(states))
        if not np.array_equal(out, want):
            return False, "permutation differs from the reference"
        return True, "ok"
    except UnknownBackendError:
        raise
    except Exception as e:  # noqa: BLE001 — lowering errors vary by platform
        return False, f"{type(e).__name__}: {e}"


def require(name: str) -> ComputeBackend:
    """The backend ``name``, after :func:`probe` passed; raises
    :class:`BackendUnavailableError` with the probe's reason otherwise."""
    ok, reason = probe(name)
    if not ok:
        raise BackendUnavailableError(f"backend {name!r} unusable: {reason}")
    return get(name)


CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, os.pardir, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the
    directory from it and nothing else is set.  Otherwise the cache lives
    at one fixed path inside the checkout (``<repo>/.jax_cache``), so every
    run of this checkout finds what an earlier run compiled.  Entry points
    call this before their first JAX computation; tests do not."""
    import jax
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.normpath(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# the three registered backends (lazy imports: this module must stay
# import-light — hashing/poly import it at module load)
# ---------------------------------------------------------------------------
def _ref_permute(states):
    from . import hashing
    return hashing.permute_ref(states)


def _ref_ntt(x, inverse: bool = False):
    from . import poly
    return poly.ntt_ref(x, inverse=inverse)


def _ref_grand_product_ext(x):
    from ..kernels.grand_product.ref import grand_product_ext_ref
    return grand_product_ext_ref(x)


def _pallas_permute(interpret: bool):
    def permute(states):
        from ..kernels import on_mesh
        from ..kernels.poseidon import ops
        return on_mesh(lambda s: ops.permute(s, interpret=interpret), states,
                       split=True)
    return permute


def _pallas_ntt(interpret: bool):
    def ntt(x, inverse: bool = False):
        from ..kernels import on_mesh
        from ..kernels.ntt import ops
        return on_mesh(lambda v: ops.ntt(v, inverse=inverse,
                                         interpret=interpret), x, split=True)
    return ntt


def _pallas_grand_product_ext(interpret: bool):
    def grand_product_ext(x):
        from ..kernels import on_mesh
        from ..kernels.grand_product import ops
        return on_mesh(lambda v: ops.grand_product_ext(v, interpret=interpret),
                       x, split=False)
    return grand_product_ext


register(ComputeBackend(
    name="ref",
    description="pure-jnp reference paths (uint64 oracle); CPU default",
    permute=_ref_permute,
    ntt=_ref_ntt,
    grand_product_ext=_ref_grand_product_ext,
    interpret=None,
))

register(ComputeBackend(
    name="pallas-interpret",
    description="Pallas kernels in interpret mode; runs on CPU/CI",
    permute=_pallas_permute(True),
    ntt=_pallas_ntt(True),
    grand_product_ext=_pallas_grand_product_ext(True),
    interpret=True,
))

register(ComputeBackend(
    name="pallas",
    description="compiled Pallas kernels; needs a TPU",
    permute=_pallas_permute(False),
    ntt=_pallas_ntt(False),
    grand_product_ext=_pallas_grand_product_ext(False),
    interpret=False,
))
